"""Merge-tree catch-up replay on device — the north-star kernel.

Re-expresses the CPU oracle's pointer-walk (dds/merge_tree.py, semantics
pinned by SEMANTICS.md) as a pure op-fold over *array-structured state*
(SURVEY.md §7 design stance): per document, a fixed-capacity segment pool
kept in sequence order as a struct-of-int32-arrays; each sequenced op is one
`lax.scan` step of fixed-shape vector work:

1. masked visible lengths for the op's view (ref_seq, client) — the
   "partial lengths" of the reference, recomputed as a masked prefix sum;
2. up to two *splits* (range/position boundaries falling inside segments),
   each a shift of the pool right by one slot past the split point — a
   static one-slot roll and a select, elementwise (a per-document
   ``take`` compiles to a general batched gather on the TPU);
3. the op body as masked updates: insert = shift + write at the tie-break
   index (first slot whose exclusive prefix ≥ pos — catch-up has no pending
   segments, so the SEMANTICS.md tie-break degenerates to exactly this);
   remove = first-wins removal marking (+ exact-timed (seq, client)
   overlap-remover slots, filled in seq order); annotate = masked
   property-column writes.

Catch-up is post-sequencing: the fold is sequential per document but
embarrassingly parallel across documents — `vmap` over the doc axis, then
pjit over a document-sharded mesh (parallel/).  Zamboni is intentionally
*absent* on device: tombstone collection never changes the visible order
(tie-break stops before tombstones; sub-window tombstones are invisible to
every reachable view), so the kernel keeps tombstones and the host-side
canonical normalizer (same one the oracle uses) drops them at summary
extraction.  Text bytes stay host-side in an arena; the device tracks
(start, len) spans only.

Interval ops don't run on device: they are folded host-side over the final
device state (ops/interval_replay.py), which retains every tombstone and so
reconstructs any historical view.  Overlapping removers of one segment keep
a (seq, client) slot each, as many slots as the chunk's own input can fill
(``ov_slot_count``, capped at ``OV_SLOT_CAP``); a document with more
removers than that (flag raised) or a base summary carrying more overlap
removers falls back to a full oracle replay — correctness is never
approximated.  Segment pool capacity = base segments + 2·ops (each op
splits ≤ 2).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..protocol.messages import MessageType, SequencedMessage
from ..protocol.summary import SummaryTree, canonical_json
from ..utils.telemetry import span
from .interning import Interner, TextArena, next_bucket, next_bucket_fine
from .native_pack import count_streams

NOT_REMOVED = np.int32(np.iinfo(np.int32).max)
# Property-column sentinels (values are interned ids >= 0).
PROP_ABSENT = -1      # key not set on the segment
PROP_NOT_TOUCHED = -2  # annotate op does not touch this key

K_NOOP, K_INSERT, K_REMOVE, K_ANNOTATE, K_OBLITERATE = 0, 1, 2, 3, 4


class MTState(NamedTuple):
    """Per-document segment pool, in sequence order (slots [0, n))."""

    tstart: jnp.ndarray      # [S] arena offset
    tlen: jnp.ndarray        # [S] span length (chars)
    ins_seq: jnp.ndarray     # [S]
    ins_client: jnp.ndarray  # [S] per-doc client idx; -1 = universal epoch
    rem_seq: jnp.ndarray     # [S] NOT_REMOVED if alive
    rem_client: jnp.ndarray  # [S] -1 if alive
    rem2_seq: jnp.ndarray    # [S] second (overlap) remover seq / NOT_REMOVED
    rem2_client: jnp.ndarray # [S] second remover client / -1
    ob1_seq: jnp.ndarray     # [S] first obliterate stamp seq / NOT_REMOVED
    ob1_client: jnp.ndarray  # [S] first stamp client / -1
    ob2_seq: jnp.ndarray     # [S] second obliterate stamp seq / NOT_REMOVED
    ob2_client: jnp.ndarray  # [S] second stamp client / -1
    props: jnp.ndarray       # [S, K] interned value ids / PROP_ABSENT
    n: jnp.ndarray           # [] live slot count
    overflow: jnp.ndarray    # [] bool: a remover found every slot full
    # Third and later removers: a (seq, client) plane pair per overlap
    # slot past the first, filled in seq order.  Empty (no leaves) unless
    # the chunk needs two or more overlap slots, so such chunks fold the
    # same pytree as a state without these fields.
    remx_seq: tuple = ()     # ([S], ...) remover seq / NOT_REMOVED
    remx_client: tuple = ()  # ([S], ...) remover client / -1


class MTOps(NamedTuple):
    """Packed op stream (scan xs), one row per sequenced op."""

    kind: jnp.ndarray     # [T]
    seq: jnp.ndarray      # [T]
    client: jnp.ndarray   # [T] per-doc client idx
    ref_seq: jnp.ndarray  # [T]
    min_seq: jnp.ndarray  # [T] stamped MSN (drives expiry parity w/ zamboni)
    a: jnp.ndarray        # [T] pos (insert) / start (remove, annotate)
    b: jnp.ndarray        # [T] end (remove, annotate)
    tstart: jnp.ndarray   # [T] arena offset of inserted text
    tlen: jnp.ndarray     # [T]
    pvals: jnp.ndarray    # [T, K] per-key values / PROP_NOT_TOUCHED


#: most overlap-remover slots a chunk folds with; a document whose
#: segment has more concurrent removers than 1 + this takes the oracle
OV_SLOT_CAP = 8


def ov_slot_count(tail_removers: np.ndarray, base_removers: np.ndarray,
                  base_overlap: int, sequential: bool) -> int:
    """The overlap-remover slots a chunk folds with, from its packed input
    alone.  A client removes a given character at most once (its own
    remove is in its view), so the distinct clients with a remove or
    obliterate row in a document's tail (``tail_removers``, per doc), plus
    the most removers any one of its base records carries
    (``base_removers``, per doc: the winner and its "ro" list), bound the
    removers of any of its segments.  One of them wins; the rest need a
    slot each.  Sequential views never see a removed segment, so only the
    base records' own overlap removers (``base_overlap``, the longest "ro"
    list) need slots there.  0 keeps the overlap planes constant; more is
    rounded up to a power of two, at most ``OV_SLOT_CAP``."""
    need = int(base_overlap)
    if not sequential and len(tail_removers):
        need = max(need, int((tail_removers + base_removers).max()) - 1)
    if need <= 0:
        return 0
    slots = 1
    while slots < need:
        slots *= 2
    return min(slots, OV_SLOT_CAP)


def tail_remover_counts(kind: np.ndarray, client: np.ndarray) -> np.ndarray:
    """Per document, the distinct clients with a remove or obliterate row
    in a packed ``[D, T]`` op stream."""
    rem = (kind == K_REMOVE) | (kind == K_OBLITERATE)
    if not rem.any():
        return np.zeros(kind.shape[0], np.int64)
    col = np.where(rem, client + 1, 0)  # client -1 (none) is a client too
    seen = np.zeros((kind.shape[0], int(col.max()) + 1), np.bool_)
    d, t = np.nonzero(rem)
    seen[d, col[d, t]] = True
    return seen.sum(axis=1)


def _visible_len(state: MTState, ref_seq, client,
                 has_ob: bool = True) -> jnp.ndarray:
    slot = jnp.arange(state.tlen.shape[0])
    active = slot < state.n
    ins_vis = (state.ins_seq <= ref_seq) | (state.ins_client == client)
    rem_vis = (
        (state.rem_seq <= ref_seq)
        | (state.rem_client == client)
        | (state.rem2_client == client)
    )
    for plane in state.remx_client:
        rem_vis = rem_vis | (plane == client)
    if has_ob:
        # An obliterate STAMP makes its author involved in the removal
        # even when another client's remove won it: the author's
        # optimistic view hid every covered slot, so views in the
        # author's name must hide the tombstone too (the oracle's
        # fuzz-found rule, merge_tree._removed_in_view; kernel gap found
        # at fuzz seed 1500041 — a lagged insert resolved 4 chars off).
        # Ob-free chunks (compile-time fact) skip the plane reads.
        removed = state.rem_seq != NOT_REMOVED
        rem_vis = rem_vis \
            | (removed & (state.ob1_client == client)) \
            | (removed & (state.ob2_client == client))
    return jnp.where(active & ins_vis & ~rem_vis, state.tlen, 0)


def _excl_cumsum(v: jnp.ndarray) -> jnp.ndarray:
    return jnp.cumsum(v) - v


def _shift_right(f: jnp.ndarray, keep: jnp.ndarray) -> jnp.ndarray:
    """The pool moved right by one slot past a prefix: slot s keeps f[s]
    where ``keep`` (a prefix mask over the slot axis, true at slot 0)
    holds and reads f[s - 1] after it, so the roll's wrapped element is
    never read.  Equal to ``jnp.take(f, where(keep, slot, slot - 1))``,
    with no gather; ``f`` is a [S] plane or the [S, K] props plane."""
    if f.ndim > 1:
        keep = keep[:, None]
    return jnp.where(keep, f, jnp.roll(f, 1, axis=0))


def _pick(f: jnp.ndarray, at: jnp.ndarray) -> jnp.ndarray:
    """``f[i]`` for the one slot i where ``at`` holds (0 where none does),
    as a masked sum: no dynamic index, so no gather."""
    return jnp.sum(jnp.where(at, f, 0), dtype=f.dtype)


def _split_at(state: MTState, char_pos, ref_seq, client, enable,
              has_ob: bool = True, has_ov: bool = True,
              has_props: bool = True) -> MTState:
    """Split the segment that ``char_pos`` falls strictly inside of (in the
    op's view), shifting the pool right by one.  No-op when the position
    lands on a boundary or ``enable`` is false.

    Constant planes are SHIFT-INVARIANT, so the chunk facts skip their
    shuffles outright: ob-free chunks never write the four ob columns
    (they stay NOT_REMOVED/-1), overlap-free chunks (``ov_slot_count``
    0) never write rem2, props-free chunks never write the [S, K]
    plane."""
    S = state.tlen.shape[0]
    v = _visible_len(state, ref_seq, client, has_ob)
    cum = _excl_cumsum(v)
    inside = (cum < char_pos) & (char_pos < cum + v)
    do = enable & inside.any()
    idx = jnp.argmax(inside)  # unique when present
    slot = jnp.arange(S)
    is_left = slot == idx
    off = char_pos - _pick(cum, is_left)
    keep = slot <= idx

    def shift(f):
        return _shift_right(f, keep)

    tstart, tlen = shift(state.tstart), shift(state.tlen)
    is_right = slot == idx + 1
    new_tlen = jnp.where(is_left, off, jnp.where(is_right, tlen - off, tlen))
    new_tstart = jnp.where(is_right, tstart + off, tstart)
    out = MTState(
        tstart=new_tstart,
        tlen=new_tlen,
        ins_seq=shift(state.ins_seq),
        ins_client=shift(state.ins_client),
        rem_seq=shift(state.rem_seq),
        rem_client=shift(state.rem_client),
        rem2_seq=shift(state.rem2_seq) if has_ov else state.rem2_seq,
        rem2_client=shift(state.rem2_client) if has_ov
        else state.rem2_client,
        ob1_seq=shift(state.ob1_seq) if has_ob else state.ob1_seq,
        ob1_client=shift(state.ob1_client) if has_ob else state.ob1_client,
        ob2_seq=shift(state.ob2_seq) if has_ob else state.ob2_seq,
        ob2_client=shift(state.ob2_client) if has_ob else state.ob2_client,
        props=shift(state.props) if has_props else state.props,
        n=state.n + 1,
        overflow=state.overflow,
        remx_seq=tuple(shift(p) for p in state.remx_seq),
        remx_client=tuple(shift(p) for p in state.remx_client),
    )
    return jax.tree.map(lambda new, old: jnp.where(do, new, old), out, state)


def _apply_op(state: MTState, op, sequential: bool = False,
              has_ob: bool = True, has_props: bool = True,
              has_ov: bool = True) -> MTState:
    """One sequenced op — the scan step.

    ``sequential`` / ``has_ob`` / ``has_props`` / ``has_ov`` are
    COMPILE-TIME chunk facts (the same
    pack-time predicates that drive the export row elisions): a fully
    sequential chunk (every ref_seq == seq-1) can never arrival-kill an
    insert (no stamp exceeds any op's ref — base stamps included, since
    they are <= base_seq <= every tail ref), and an obliterate-free chunk
    never stamps — so the arrival-kill scan and the stamping block trace
    away instead of running masked-dead every step.  A chunk with NO
    property keys
    anywhere (no annotate ops, no base props — pack's interner is empty)
    keeps its constant PROP_ABSENT plane untouched: the per-op [S, K]
    plane shift and the annotate write trace away.  ``has_ov=False``
    (``ov_slot_count`` 0: no segment of the chunk can have a second
    remover — a sequential remove never even targets an already-removed
    segment, it is invisible in the remover's view) keeps the two rem2
    planes constant: their shifts and the overlap writes trace away.
    Otherwise the overlap slots are rem2 and the ``remx_*`` plane pairs
    the state carries (their count is the state's own structure): a later
    remover takes the first free slot, and only a remover that finds
    every slot full raises ``overflow``."""
    S = state.tlen.shape[0]
    ref_seq, client = op.ref_seq, op.client
    is_ins = op.kind == K_INSERT
    is_rem = op.kind == K_REMOVE
    is_ann = op.kind == K_ANNOTATE
    is_obl = op.kind == K_OBLITERATE
    is_rangey = is_rem | is_ann | is_obl

    # Boundary splits (shared by all op kinds).
    state = _split_at(state, op.a, ref_seq, client, is_ins | is_rangey,
                      has_ob, has_ov, has_props)
    state = _split_at(state, op.b, ref_seq, client, is_rangey,
                      has_ob, has_ov, has_props)

    v = _visible_len(state, ref_seq, client, has_ob)
    cum = _excl_cumsum(v)
    slot = jnp.arange(S)
    active = slot < state.n
    # Zamboni parity: slots the oracle has physically collected by this
    # fold position (expired tombstones at the op's stamped MSN) must act
    # as ABSENT — never stamped, never a neighbor in the arrival scan.
    msn = op.min_seq
    ob1_live = (state.ob1_seq != NOT_REMOVED) & (state.ob1_seq > msn)
    ob2_live = (state.ob2_seq != NOT_REMOVED) & (state.ob2_seq > msn)
    expired = (
        (state.rem_seq != NOT_REMOVED) & (state.rem_seq <= msn)
        & (state.ins_seq <= msn) & ~ob1_live & ~ob2_live
    )

    # --- insert: tie-break index = first slot with cum >= pos (catch-up has
    # no pending segments; stop before the first sequenced segment).
    can = (cum >= op.a) & active
    j = jnp.where(can.any(), jnp.argmax(can), state.n)

    if sequential or not has_ob:
        # No stamp can exceed a sequential op's ref (and without
        # obliterates there are no stamps at all): arrival kills are
        # structurally impossible — the whole neighbor scan traces away.
        kill_seq = jnp.int32(NOT_REMOVED)
        kill_client = jnp.int32(-1)
        killed = jnp.bool_(False)
    else:
        # Obliterate-on-arrival (see dds/merge_tree.py docstring): the
        # insert dies iff its pool neighbors share a stamp the inserter
        # had not seen from another client; the EARLIEST shared stamp is
        # the remover.  Neighbors = nearest NON-EXPIRED slots around the
        # tie-break index.
        present = active & ~expired
        left_idx = jnp.max(jnp.where(present & (slot < j), slot, -1))
        right_idx = jnp.min(jnp.where(present & (slot >= j), slot, S))

        def stamp_at(f, idx, valid):
            return jnp.where(valid, _pick(f, slot == idx),
                             jnp.int32(NOT_REMOVED))

        has_left = left_idx >= 0
        has_right = right_idx < S
        l1s = stamp_at(state.ob1_seq, left_idx, has_left)
        l2s = stamp_at(state.ob2_seq, left_idx, has_left)
        l1c = stamp_at(state.ob1_client, left_idx, has_left)
        l2c = stamp_at(state.ob2_client, left_idx, has_left)
        r1s = stamp_at(state.ob1_seq, right_idx, has_right)
        r2s = stamp_at(state.ob2_seq, right_idx, has_right)

        def killer_of(ls, lc):
            shared = (ls != NOT_REMOVED) & ((ls == r1s) | (ls == r2s))
            ok = shared & (ls > ref_seq) & (lc != client)
            return jnp.where(ok, ls, jnp.int32(NOT_REMOVED)), lc

        k1s, k1c = killer_of(l1s, l1c)
        k2s, k2c = killer_of(l2s, l2c)
        kill_seq = jnp.minimum(k1s, k2s)
        kill_client = jnp.where(k1s <= k2s, k1c, k2c)
        killed = kill_seq != NOT_REMOVED

    keep = slot <= j

    def shifted(f, newval):
        moved = _shift_right(f, keep)
        if f.ndim == 1:
            return jnp.where(slot == j, newval, moved)
        return jnp.where((slot == j)[:, None], newval, moved)

    ins_state = MTState(
        tstart=shifted(state.tstart, op.tstart),
        tlen=shifted(state.tlen, op.tlen),
        ins_seq=shifted(state.ins_seq, op.seq),
        ins_client=shifted(state.ins_client, client),
        rem_seq=shifted(state.rem_seq,
                        jnp.where(killed, kill_seq, NOT_REMOVED)),
        rem_client=shifted(state.rem_client,
                           jnp.where(killed, kill_client, -1)),
        # Constant planes are shift-invariant (new slots get the same
        # constant): skip their shifts under the facts.
        rem2_seq=shifted(state.rem2_seq, NOT_REMOVED) if has_ov
        else state.rem2_seq,
        rem2_client=shifted(state.rem2_client, -1) if has_ov
        else state.rem2_client,
        ob1_seq=shifted(state.ob1_seq,
                        jnp.where(killed, kill_seq, NOT_REMOVED))
        if has_ob else state.ob1_seq,
        ob1_client=shifted(state.ob1_client,
                           jnp.where(killed, kill_client, -1))
        if has_ob else state.ob1_client,
        ob2_seq=shifted(state.ob2_seq, NOT_REMOVED) if has_ob
        else state.ob2_seq,
        ob2_client=shifted(state.ob2_client, -1) if has_ob
        else state.ob2_client,
        # A constant PROP_ABSENT plane is shift-invariant: skip the
        # shift+where entirely on props-free chunks.
        props=shifted(
            state.props,
            jnp.where(op.pvals == PROP_NOT_TOUCHED, PROP_ABSENT, op.pvals),
        ) if has_props else state.props,
        n=state.n + 1,
        overflow=state.overflow,
        remx_seq=tuple(shifted(p, NOT_REMOVED) for p in state.remx_seq),
        remx_client=tuple(shifted(p, -1) for p in state.remx_client),
    )
    state = jax.tree.map(
        lambda new, old: jnp.where(is_ins, new, old), ins_state, state
    )

    # --- remove / annotate / obliterate target: segments fully inside
    # [a, b) in the view (splits above made partial overlaps exact).
    # Computed on the pre-insert cum/v, which is correct because the masks
    # are exclusive by kind.
    covered = (cum >= op.a) & (cum + v <= op.b) & (v > 0) & active

    is_rem_like = is_rem | is_obl
    first_win = covered & (state.rem_seq == NOT_REMOVED) & is_rem_like
    again = covered & (state.rem_seq != NOT_REMOVED) & is_rem_like
    # Overlap slots fill in seq order (occupied slots are a prefix): a
    # later remover takes the first free one; ``full`` is left holding
    # the segments where every slot was taken.
    takes = []
    full = again
    for plane in (state.rem2_seq,) + state.remx_seq:
        takes.append(full & (plane == NOT_REMOVED))
        full = full & (plane != NOT_REMOVED)
    if has_ob:
        # Obliterate additionally stamps zero-width slots strictly inside
        # the range: tombstones (stamp only) and invisible concurrent
        # inserts (remove + stamp) — the oracle's zero-width pass.  Two
        # stamp slots; a third distinct obliterate on one slot overflows
        # to the oracle.
        obl_zero = active & ~expired & (v == 0) \
            & (cum > op.a) & (cum < op.b) & is_obl
        obl_zero_alive = obl_zero & (state.rem_seq == NOT_REMOVED)
        first_win = first_win | obl_zero_alive
        stamp = (covered & is_obl) | obl_zero
        to_ob1 = stamp & (state.ob1_seq == NOT_REMOVED)
        to_ob2 = stamp & ~to_ob1 & (state.ob2_seq == NOT_REMOVED) \
            & (state.ob1_seq != op.seq)
        ob_over = stamp & (state.ob1_seq != NOT_REMOVED) \
            & (state.ob2_seq != NOT_REMOVED) \
            & (state.ob1_seq != op.seq) & (state.ob2_seq != op.seq)
        state = state._replace(
            ob1_seq=jnp.where(to_ob1, op.seq, state.ob1_seq),
            ob1_client=jnp.where(to_ob1, client, state.ob1_client),
            ob2_seq=jnp.where(to_ob2, op.seq, state.ob2_seq),
            ob2_client=jnp.where(to_ob2, client, state.ob2_client),
            overflow=state.overflow | ob_over.any(),
        )
    state = state._replace(
        rem_seq=jnp.where(first_win, op.seq, state.rem_seq),
        rem_client=jnp.where(first_win, client, state.rem_client),
    )
    if has_ov:
        # No second remover possible (has_ov=False): a remove or
        # obliterate can never target an already-removed segment, so
        # every ``takes`` mask is structurally false — rem2 stays
        # constant and these writes trace away.
        state = state._replace(
            rem2_seq=jnp.where(takes[0], op.seq, state.rem2_seq),
            rem2_client=jnp.where(takes[0], client, state.rem2_client),
            remx_seq=tuple(jnp.where(t, op.seq, p)
                           for t, p in zip(takes[1:], state.remx_seq)),
            remx_client=tuple(jnp.where(t, client, p)
                              for t, p in zip(takes[1:], state.remx_client)),
            overflow=state.overflow | full.any(),
        )

    if has_props:
        touch = (op.pvals != PROP_NOT_TOUCHED)[None, :] \
            & (covered & is_ann)[:, None]
        state = state._replace(
            props=jnp.where(
                touch, jnp.broadcast_to(op.pvals, state.props.shape),
                state.props)
        )
    return state


def replay_scan(state: MTState, ops: MTOps, sequential: bool = False,
                has_ob: bool = True, has_props: bool = True,
                has_ov: bool = True) -> MTState:
    """Pure single-document op-fold (no jit): scan the op stream.
    ``sequential``/``has_ob``/``has_props``/``has_ov`` are compile-time
    chunk facts (see ``_apply_op``); the defaults are the full
    semantics.  The overlap slots are the ones ``state`` carries."""

    def step(carry, op):
        return _apply_op(carry, op, sequential, has_ob, has_props,
                         has_ov), None

    final, _ = jax.lax.scan(step, state, ops)
    return final


def replay_vmapped(state: MTState, ops: MTOps, sequential: bool = False,
                   has_ob: bool = True, has_props: bool = True,
                   has_ov: bool = True) -> MTState:
    """Vmapped over the document axis — the unit the parallel/ package
    shards."""
    return jax.vmap(
        lambda s, o: replay_scan(s, o, sequential, has_ob, has_props,
                                 has_ov)
    )(state, ops)



def _cold_start(ops: "MTOps", S: int, ov_slots: int = 1) -> "MTState":
    """Empty initial state built IN-GRAPH: documents with no base summary
    start from all zeros/sentinels — constructing it on device instead of
    transferring (D, S) arrays of zeros cuts the per-chunk upload to the op
    arrays alone.  ``ov_slots`` overlap slots: rem2, then a ``remx_*``
    plane pair for each slot past the first."""
    D = ops.kind.shape[0]
    K = ops.pvals.shape[2]
    extra = max(ov_slots - 1, 0)
    return MTState(
        tstart=jnp.zeros((D, S), jnp.int32),
        tlen=jnp.zeros((D, S), jnp.int32),
        ins_seq=jnp.zeros((D, S), jnp.int32),
        ins_client=jnp.full((D, S), -1, jnp.int32),
        rem_seq=jnp.full((D, S), NOT_REMOVED, jnp.int32),
        rem_client=jnp.full((D, S), -1, jnp.int32),
        rem2_seq=jnp.full((D, S), NOT_REMOVED, jnp.int32),
        rem2_client=jnp.full((D, S), -1, jnp.int32),
        ob1_seq=jnp.full((D, S), NOT_REMOVED, jnp.int32),
        ob1_client=jnp.full((D, S), -1, jnp.int32),
        ob2_seq=jnp.full((D, S), NOT_REMOVED, jnp.int32),
        ob2_client=jnp.full((D, S), -1, jnp.int32),
        props=jnp.full((D, S, K), PROP_ABSENT, jnp.int32),
        n=jnp.zeros((D,), jnp.int32),
        overflow=jnp.zeros((D,), jnp.bool_),
        remx_seq=tuple(jnp.full((D, S), NOT_REMOVED, jnp.int32)
                       for _ in range(extra)),
        remx_client=tuple(jnp.full((D, S), -1, jnp.int32)
                          for _ in range(extra)),
    )


@functools.partial(jax.jit, static_argnums=(1,))
def _replay_batch_cold(ops: "MTOps", S: int) -> "MTState":
    return replay_vmapped(_cold_start(ops, S), ops)


# Export row layout: per-slot fields stacked into ONE array so the
# device→host copy costs a single transfer per fold (each transfer pays a
# fixed latency — ten small arrays cost 10× one fused array in round 2).
# Rows 0..11 are the slot fields, rows 12..12+K-1 the property columns,
# then one (seq, client) row pair per overlap slot past the first
# (``ov_extra_fields``: none unless the chunk folds with two or more), and
# the final row is misc: [n, overflow, live_len].
#
# Two element widths exist.  The int32 layout is the always-correct default;
# when every value a chunk can produce fits in int16 (pack-time check:
# head seq, per-doc text chars, S, intern-table sizes all < 2**15-1 —
# ``meta['i16_ok']``) the export is emitted as int16 with two transforms the
# host inverts after download (``widen_export``): text offsets are rebased
# per document (``tstart - doc_base[d]``; a doc's arena spans are contiguous
# because packing appends per doc) and NOT_REMOVED maps to I16_NOT_REMOVED.
# Halving the element width halves the device→host bytes, the leg that
# dominated the round-2 chip run.
EXPORT_SLOT_FIELDS = (
    "tstart", "tlen", "ins_seq", "ins_client",
    "rem_seq", "rem_client", "rem2_seq", "rem2_client",
    "ob1_seq", "ob1_client", "ob2_seq", "ob2_client",
)
#: the slot fields with no obliterate content — the export layout when a
#: chunk provably carries no obliterates (``meta["ob_rows"]`` False)
NON_OB_SLOT_FIELDS = EXPORT_SLOT_FIELDS[:8]
#: the obliterate rows elided from such exports, with their sentinel fills
OB_SLOT_FIELDS = EXPORT_SLOT_FIELDS[8:]
#: the first overlap slot's rows, elided (``meta["ov_slots"]`` 0) when
#: no segment of the chunk can have a second remover (``ov_slot_count``)
OV_SLOT_FIELDS = ("rem2_seq", "rem2_client")
#: rows holding seqs with the NOT_REMOVED sentinel (narrow remap set)
SENTINEL_SEQ_FIELDS = ("rem_seq", "rem2_seq", "ob1_seq", "ob2_seq")


def _is_sentinel_seq(field: str) -> bool:
    """A seq row with the NOT_REMOVED sentinel: every seq row but the
    insert's, the overlap slots past the first (``ov_extra_fields``)
    among them."""
    return field.endswith("_seq") and field != "ins_seq"


I16_NOT_REMOVED = np.int16(np.iinfo(np.int16).max)
I16_LIMIT = int(np.iinfo(np.int16).max) - 1  # strict value bound for i16_ok
#: int8 pair-packing (``meta["i8_ok"]``): when every exported value other
#: than tstart/misc fits in a signed byte, pairs of slot/prop rows pack
#: into one int16 lane each — byte rows halve on the wire.
I8_NOT_REMOVED = np.int32(127)
I8_LIMIT = 126


def _export_fields(ob_rows: bool, ov_slots: int):
    fields = list(EXPORT_SLOT_FIELDS if ob_rows else NON_OB_SLOT_FIELDS)
    if not ov_slots:
        fields = [f for f in fields if f not in OV_SLOT_FIELDS]
    return fields


def ov_extra_fields(ov_slots: int) -> List[str]:
    """The export rows of the overlap slots past the first, after the
    props rows: ``rem3_seq, rem3_client, rem4_seq, ...``."""
    return [f"rem{j}_{part}" for j in range(3, ov_slots + 2)
            for part in ("seq", "client")]


def _export_state(final: MTState, doc_base: Optional[jnp.ndarray] = None,
                  i16: bool = False, ob_rows: bool = True,
                  ov_slots: int = 1, i8: bool = False,
                  props_rows: bool = True) -> jnp.ndarray:
    """[D, rows, S] fused view of everything summary extraction and
    interval replay need from the final device state (int32, or int16 when
    ``i16`` with per-doc-rebased tstart and remapped NOT_REMOVED
    sentinels).

    Transfer-shrinking layouts, each undone host-side by ``widen_export``
    (the device→host fetch is the pipeline's measured bottleneck):
    - ``ob_rows=False``: the four obliterate rows elided (no obliterate
      ops or base stamps in the chunk — pack-time fact);
    - ``ov_slots=0``: the two overlap-remover rows elided (no segment of
      the chunk can have a second remover); each slot past the first
      adds a row pair after the props rows;
    - ``props_rows=False``: the K props-plane rows elided (props-free
      chunk — the plane is constant PROP_ABSENT);
    - ``i8``: every byte-sized row pairs into one int16 lane
      (``(a & 0xFF) << 8 | (b & 0xFF)``) — tstart and misc stay 16-bit."""
    i8 = i8 and i16  # byte packing presupposes the int16 transforms
    D, S = final.tlen.shape
    K = final.props.shape[2]
    extra = ov_extra_fields(ov_slots)
    assert len(extra) == 2 * len(final.remx_seq), "ov_slots != state"
    slot = jnp.arange(S)[None, :]
    active = slot < final.n[:, None]
    live = jnp.where(
        active & (final.rem_seq == NOT_REMOVED), final.tlen, 0,
    ).sum(axis=1)
    misc = jnp.zeros((D, S), jnp.int32)
    misc = misc.at[:, 0].set(final.n)
    misc = misc.at[:, 1].set(final.overflow.astype(jnp.int32))
    misc = misc.at[:, 2].set(live)
    # Slots beyond n hold shift leftovers no consumer reads; zero their
    # tstart in BOTH widths so the two exports are bit-equivalent after
    # ``widen_export`` (and export bytes are deterministic).
    tstart = jnp.where(active, final.tstart, 0)
    named = {"tstart": tstart}
    named.update(zip(extra, (p for pair in zip(final.remx_seq,
                                               final.remx_client)
                             for p in pair)))
    fields = _export_fields(ob_rows, ov_slots)
    if i16:
        named["tstart"] = jnp.where(active, tstart - doc_base[:, None], 0)
        sentinel = I8_NOT_REMOVED if i8 else jnp.int32(I16_NOT_REMOVED)
        for f in fields + extra:
            if not _is_sentinel_seq(f):
                continue
            val = named[f] if f in named else getattr(final, f)
            named[f] = jnp.where(val == NOT_REMOVED, sentinel, val)
    rows = [named.get(f, getattr(final, f)) for f in fields]
    if props_rows:
        rows += [final.props[:, :, k] for k in range(K)]
    rows += [named[f] for f in extra]
    if i8:
        byte_rows = rows[1:]
        if len(byte_rows) % 2:
            byte_rows.append(jnp.zeros((D, S), jnp.int32))
        packed = [
            ((byte_rows[i] & 0xFF) << 8) | (byte_rows[i + 1] & 0xFF)
            for i in range(0, len(byte_rows), 2)
        ]
        rows = [rows[0]] + packed
        # The misc values (n, overflow, live_len) ride a SEPARATE tiny
        # [D, 4] int32 output instead of a full S-column row — one less
        # row off the dominant fetch; widen_export stitches the canonical
        # misc row back host-side.
        out = jnp.stack(rows, axis=1).astype(jnp.int16)  # bound: i16_ok
        return out, misc[:, :4]
    rows.append(misc)
    out = jnp.stack(rows, axis=1)
    return out.astype(jnp.int16) if i16 else out  # bound: i16_ok


def export_to_numpy(export):
    """Fetch an export handle to numpy — the i8 layout is a
    ``(slot_rows, misc)`` pair of device buffers; other layouts a single
    fused buffer."""
    if isinstance(export, tuple):
        return tuple(np.asarray(x) for x in export)
    return np.asarray(export)


# ---------------------------------------------------------------------------
# Per-doc state digests (digest-gated delta download — ISSUE 6)
# ---------------------------------------------------------------------------

#: fixed per-plane salt ids for the digest mix.  Stable across layouts:
#: the digest reads the CANONICAL final state, never the transfer buffer,
#: so bucket growth / row elisions / byte packing cannot perturb it.
_DIGEST_PLANES = tuple(EXPORT_SLOT_FIELDS)
_DIGEST_PROPS_BASE = 16  # props column k salts at 16 + k
_DIGEST_OV_BASE = 1 << 20  # remx slot j salts seq at this + 2j, client + 1


def _mix_u32(x: jnp.ndarray) -> jnp.ndarray:
    """splitmix-style avalanche over uint32 lanes (wraparound on purpose;
    runs in-graph on device)."""
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _doc_digests(final: MTState, doc_base: jnp.ndarray) -> jnp.ndarray:
    """``[D, 2]`` int32 digest of each document's canonical final state —
    the device-computed summary identity the delta-download path compares
    before deciding which documents' export rows must cross the d2h link.

    Properties the delta path relies on (pinned by tests):

    - **masked**: only live slots (``slot < n``) contribute — dead-slot
      shift leftovers (which legitimately differ between a fresh pack and
      a suffix-extended one) never reach the hash;
    - **rebased**: ``tstart`` enters relative to the doc's arena base, so
      a document whose own bytes are unchanged digests identically even
      when other documents in the chunk moved its absolute arena offsets;
    - **bucket-invariant**: weights are per (plane, slot-index), so S/T
      padding growth around an unchanged document cannot perturb it; a
      props key the document never set contributes ZERO (set values hash
      shifted by +1 — intern ids are >= 0, so "value 0" stays distinct
      from "absent"), so K-bucket growth (another doc's new annotate
      key) cannot perturb it either; likewise an empty overlap slot past
      the first contributes ZERO, so another doc's third remover (more
      ``remx_*`` planes in the chunk) cannot perturb it, while every
      occupied slot is mixed in;
    - 64 bits across two independently-salted lanes — a collision (the
      only way delta download could serve wrong bytes for inputs the
      host-side anchor check cannot distinguish) is a ~2^-64 event, and
      every structural failure (missing entry, anchor drift, digest
      mismatch) falls back to the full download.
    """
    D, S = final.tlen.shape
    K = final.props.shape[2]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    active = slot < final.n[:, None]
    live_len = jnp.where(
        active & (final.rem_seq == NOT_REMOVED), final.tlen, 0
    ).sum(axis=1)
    slot_u = slot.astype(jnp.uint32)
    accs = []
    for lane_salt in (jnp.uint32(0x9E3779B9), jnp.uint32(0x85EBCA6B)):
        acc = jnp.zeros((D,), jnp.uint32)
        for i, f in enumerate(_DIGEST_PLANES):
            plane = getattr(final, f)
            if f == "tstart":
                plane = plane - doc_base[:, None]
            v = jnp.where(active, plane, 0).astype(jnp.uint32)
            w = _mix_u32(slot_u * jnp.uint32(0x01000193)
                         + jnp.uint32(i) + lane_salt)
            acc = acc + (v * w).sum(axis=1, dtype=jnp.uint32)
        for k in range(K):
            plane = final.props[:, :, k]
            # Absent keys hash 0 (K-bucket invariance); set values shift
            # +1 so an explicit intern id 0 stays distinct from absent.
            v = jnp.where(active & (plane != PROP_ABSENT), plane + 1,
                          0).astype(jnp.uint32)
            w = _mix_u32(slot_u * jnp.uint32(0x01000193)
                         + jnp.uint32(_DIGEST_PROPS_BASE + k) + lane_salt)
            acc = acc + (v * w).sum(axis=1, dtype=jnp.uint32)
        for j, (seq, cl) in enumerate(zip(final.remx_seq,
                                          final.remx_client)):
            # An empty slot hashes 0; a taken one mixes its seq and its
            # client + 2 (never 0: the universal client is -1).
            taken = active & (seq != NOT_REMOVED)
            for h, v in enumerate((seq, cl + 2)):
                v = jnp.where(taken, v, 0).astype(jnp.uint32)
                w = _mix_u32(slot_u * jnp.uint32(0x01000193)
                             + jnp.uint32(_DIGEST_OV_BASE + 2 * j + h)
                             + lane_salt)
                acc = acc + (v * w).sum(axis=1, dtype=jnp.uint32)
        acc = acc ^ _mix_u32(final.n.astype(jnp.uint32) + lane_salt)
        acc = acc ^ _mix_u32(live_len.astype(jnp.uint32) * jnp.uint32(3)
                             + lane_salt)
        acc = acc ^ jnp.where(final.overflow, jnp.uint32(0x5BD1E995),
                              jnp.uint32(0))
        accs.append(_mix_u32(acc))
    return jax.lax.bitcast_convert_type(
        jnp.stack(accs, axis=-1), jnp.int32)


def split_export_digest(export, digested: bool):
    """``(core, digest_or_None)`` for a ``replay_export`` handle.  With
    ``digest=True`` the digest rides as the LAST leaf of the returned
    tuple; the core keeps the exact shape the non-digest path produces
    (bare buffer, or ``(rows, misc)`` for i8 layouts) so every
    downstream consumer is unchanged."""
    if not digested:
        return export, None
    assert isinstance(export, tuple) and len(export) >= 2
    core = export[0] if len(export) == 2 else export[:-1]
    return core, export[-1]


@jax.jit
def export_gather(a: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """The delta download's device gather of changed documents' export
    rows (both families)."""
    return jnp.take(a, idx, axis=0)


def _host_view(a) -> Optional[np.ndarray]:
    """Zero-copy numpy view of a computed single-CPU-device array, or
    None when the buffer is not host-reachable.  On the CPU backend the
    "d2h link" IS host memory: a dlpack view + numpy row copy fetches
    exactly the requested rows with no XLA dispatch (a per-shape device
    gather would pay a ~0.5 s compile that swamps the bytes it saves)."""
    try:
        devs = a.devices()
        if len(devs) != 1 or next(iter(devs)).platform != "cpu":
            return None
        return np.from_dlpack(a)
    except Exception:
        return None


def gather_export_rows(export, idx: np.ndarray):
    """Fetch ONLY the documents in ``idx`` from a device export handle —
    the delta-download fetch.  Returns ``(rows, moved_bytes)`` where each
    leaf of ``rows`` has exactly ``len(idx)`` doc rows and ``moved_bytes``
    counts what actually crossed the d2h link.  On CPU-resident buffers
    this is a direct row copy out of a zero-copy host view; on
    accelerators it is a tiny in-graph gather along the doc axis (``idx``
    padded to a fine bucket internally so the gather's jit cache stays
    bounded — the pad rows DO cross, and are counted) followed by the
    d2h copy of just those rows."""
    leaves = export if isinstance(export, tuple) else (export,)
    rows = np.asarray(idx, np.intp)
    m = rows.shape[0]
    out, moved = [], 0
    dev_idx = None
    for a in leaves:
        view = _host_view(a)
        if view is not None:
            got = view[rows]
            moved += got.nbytes
        elif next_bucket_fine(m, floor=8) >= a.shape[0]:
            # The padded device gather would move as many rows as the
            # buffer holds: fetch full and slice host-side (no gather
            # dispatch).  Accelerator economics only — the host-view
            # branch above always copies exact rows.
            full = np.asarray(a)
            moved += full.nbytes
            got = full[rows]
        else:
            if dev_idx is None:
                pad = next_bucket_fine(m, floor=8) - m
                padded = np.concatenate(
                    [rows, np.repeat(rows[-1:], pad)]) if pad else rows
                dev_idx = jnp.asarray(padded, jnp.int32)
            dev = export_gather(a, dev_idx)  # bucketed-by: next_bucket_fine
            full = np.asarray(dev)
            moved += full.nbytes
            got = full[:m]
        out.append(got)
    return (tuple(out) if isinstance(export, tuple) else out[0]), moved


def _widen_desc(ob_rows: bool, ov_slots: int, i8: bool, props_rows: bool,
                n_props: int):
    """The per-canonical-row descriptor table oppack_widen consumes:
    [mode, arg, fill, flags] × (13 + K + 2·extra slots) rows.  Mirrors
    widen_export's field order exactly (same _export_fields
    derivation)."""
    fields = _export_fields(ob_rows, ov_slots)
    extra = ov_extra_fields(ov_slots)
    K_src = n_props if props_rows else 0

    def src(i: int):
        """(mode, arg) of transfer row ``i`` (the source layout: fields,
        props, extra overlap rows)."""
        if not i8:
            return 1, i                                     # ROW16
        if i == 0:
            return 1, 0                                     # 16-bit lane
        b = i - 1                                           # byte index
        return 2, (1 + b // 2) * 2 + (b % 2)                # PAIR8

    desc = []
    for f in EXPORT_SLOT_FIELDS:
        if f in fields:
            mode, arg = src(fields.index(f))
            flags = (1 if _is_sentinel_seq(f) else 0) \
                | (2 if f == "tstart" else 0)
            desc.append((mode, arg, 0, flags))
        else:
            fill = int(NOT_REMOVED) if f.endswith("_seq") else -1
            desc.append((0, 0, fill, 0))
    for k in range(n_props):
        if props_rows:
            desc.append(src(len(fields) + k) + (0, 0))
        else:
            desc.append((0, 0, int(PROP_ABSENT), 0))
    for e, f in enumerate(extra):
        mode, arg = src(len(fields) + K_src + e)
        desc.append((mode, arg, 0, 1 if _is_sentinel_seq(f) else 0))
    if i8:
        desc.append((3, 0, 0, 0))                           # stitched misc
    else:
        n_src = len(fields) + K_src + len(extra) + 1
        desc.append((1, n_src - 1, 0, 0))                   # misc row
    return np.asarray(desc, np.int32).reshape(-1)


def widen_export_native(export_np, doc_base, ob_rows: bool, ov_slots: int,
                        i8: bool, n_props: int, props_rows: bool):
    """C++ single-pass widen of a narrow export buffer to the canonical
    [D, 13+K+2·extra, S] int32 layout — byte-identical to
    ``widen_export`` (pinned by tests), ~10× faster on the extraction hot
    path.  Returns None when inapplicable (already int32, or no native
    library)."""
    from .native_pack import load_library

    misc_np = None
    if isinstance(export_np, tuple):
        export_np, misc_np = export_np
    if export_np.dtype != np.int16:
        return None
    lib = load_library()
    if lib is None:
        return None
    D, R_src, S = export_np.shape
    desc = _widen_desc(ob_rows, ov_slots, i8, props_rows, n_props)
    R_canon = len(desc) // 4
    dst = np.empty((D, R_canon, S), np.int32)
    src = np.ascontiguousarray(export_np, np.int16)
    if i8:
        assert misc_np is not None, "i8 widen needs the misc output"
        misc = np.ascontiguousarray(misc_np, np.int16)
        misc_ptr, misc_cols = misc.ctypes.data, misc.shape[1]
    else:
        misc = None
        misc_ptr, misc_cols = None, 0
    base = None if doc_base is None else \
        np.ascontiguousarray(doc_base, np.int32)
    sentinel_src = int(I8_NOT_REMOVED) if i8 else int(I16_NOT_REMOVED)
    rc = lib.oppack_widen(
        src, D, S, R_src, R_canon, misc_ptr, misc_cols, desc,
        None if base is None else base.ctypes.data,
        sentinel_src, int(NOT_REMOVED), dst,
    )
    if rc != 0:
        raise ValueError("oppack_widen: malformed narrow export")
    return dst


def widen_export(export_np,
                 doc_base: Optional[np.ndarray],
                 ob_rows: bool = True, ov_slots: int = 1,
                 i8: bool = False,
                 n_props: Optional[int] = None,
                 props_rows: bool = True) -> np.ndarray:
    """Undo the export transfer transforms host-side, always returning the
    CANONICAL full int32 layout: unpack int8 pairs and stitch the separate
    misc output back into a row (``i8`` — needs ``n_props``, the padded
    props-plane width), widen int16 to int32, restore NOT_REMOVED
    sentinels, re-add per-doc arena bases, and reinsert elided
    obliterate/overlap/props rows with their sentinel fills.  Full-layout
    int32 buffers pass through untouched."""
    misc_np = None
    if isinstance(export_np, tuple):
        export_np, misc_np = export_np
    fields = _export_fields(ob_rows, ov_slots)
    extra = ov_extra_fields(ov_slots)
    if export_np.dtype == np.int32:
        out = export_np
    else:
        K_src = (n_props if props_rows else 0) if i8 else \
            export_np.shape[1] - len(fields) - len(extra) - 1
        if i8:
            # Unpack byte pairs back into the (elided) int16-equivalent
            # row layout: [tstart, byte rows...] + the stitched misc row.
            assert n_props is not None, "i8 widen needs the props width"
            assert misc_np is not None, "i8 widen needs the misc output"
            u = export_np.astype(np.uint16)
            n_bytes = len(fields) - 1 + K_src + len(extra)
            rows = [export_np[:, 0, :].astype(np.int32)]
            for i in range(n_bytes):
                pair = u[:, 1 + i // 2, :]
                half = (pair >> 8) if i % 2 == 0 else (pair & 0xFF)
                rows.append(half.astype(np.uint8).astype(np.int8)
                            .astype(np.int32))
            D, _R, S = export_np.shape
            misc_row = np.zeros((D, S), np.int32)
            misc_row[:, :misc_np.shape[1]] = misc_np
            rows.append(misc_row)
            out = np.stack(rows, axis=1)
        else:
            out = export_np.astype(np.int32)
        sentinel = int(I8_NOT_REMOVED) if i8 else int(I16_NOT_REMOVED)
        names = fields + [None] * K_src + extra
        for i, f in enumerate(names):
            if f is None or not _is_sentinel_seq(f):
                continue
            row = out[:, i, :]
            row[row == sentinel] = NOT_REMOVED
        if doc_base is not None:
            # Re-add the per-doc arena base to live slots only (slots
            # beyond n were zeroed on device and must stay zero to match
            # the int32 path).
            n = out[:, -1, 0]
            active = np.arange(out.shape[2])[None, :] < n[:, None]
            out[:, 0, :] += np.where(
                active, np.asarray(doc_base, np.int32)[:, None], 0
            )
    def reinsert(buf, fill_fields, split):
        D, _R, S = buf.shape
        filler = np.empty((D, len(fill_fields), S), np.int32)
        for i, f in enumerate(fill_fields):
            filler[:, i, :] = NOT_REMOVED if f.endswith("_seq") else -1
        return np.concatenate(
            [buf[:, :split], filler, buf[:, split:]], axis=1
        )

    if not props_rows:
        # Reinsert the constant PROP_ABSENT plane rows after the slot
        # rows (before any extra overlap rows and the misc row).
        assert n_props is not None, "props-row reinsert needs the width"
        D, _R, S = out.shape
        filler = np.full((D, n_props, S), PROP_ABSENT, np.int32)
        out = np.concatenate([out[:, :len(fields)], filler,
                              out[:, len(fields):]], axis=1)
    if not ov_slots:
        out = reinsert(out, OV_SLOT_FIELDS,
                       fields.index("rem_client") + 1)  # rem2 slots next
    if not ob_rows:
        out = reinsert(out, OB_SLOT_FIELDS, len(NON_OB_SLOT_FIELDS))
    return out


def _fetch_format(sharding=None):
    """A Format forcing the default row-major layout on export outputs.

    The jit-chosen device layout made the device-to-host fetch ~20× slower
    than the same bytes in the default layout (10.65s vs 0.58s, round 2);
    copying into the default layout on the device first keeps the fetch
    at copy rate.  ``sharding`` is the placement the export is built for
    (the mesh export step passes its doc-sharded NamedSharding); None is
    the default device.  The decision reads the platform of THAT
    placement's devices: None only for the CPU, which has no layouts to
    force."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    if sharding is None:
        sharding = SingleDeviceSharding(jax.devices()[0])
    if next(iter(sharding.device_set)).platform == "cpu":
        return None
    return Format(Layout(major_to_minor=(0, 1, 2)), sharding)


def _out_shardings_for(i8: bool, sharding=None, digest: bool = False):
    """out_shardings matching the export's output structure: the fused 3-D
    buffer gets the forced row-major Format; the tiny [D, 4] misc output
    (i8 layouts only) and the [D, 2] digest plane get 2-D ones.
    ``sharding`` threads through to ``_fetch_format`` for the mesh
    path."""
    fmt = _fetch_format(sharding)
    if fmt is None:
        return None
    if not i8 and not digest:
        return fmt
    from jax.experimental.layout import Format, Layout

    fmt2 = Format(Layout(major_to_minor=(0, 1)), fmt.sharding)
    out = [fmt] + ([fmt2] if i8 else []) + ([fmt2] if digest else [])
    return tuple(out)


def _fold_fn(sequential: bool = False, has_ob: bool = True,
             has_props: bool = True, has_ov: bool = True):
    """The batch fold: the lax.scan path, specialized at compile time by
    the chunk facts (see ``_apply_op``)."""
    return lambda state, ops: replay_vmapped(state, ops, sequential,
                                             has_ob, has_props, has_ov)


def _export_out(i8: bool, sharding=None, digest: bool = False):
    """out_shardings for an export jit: the forced fetch layout when the
    backend supports layouts (carried on ``sharding`` when given — the
    mesh path), else the bare sharding, else None."""
    fmt = _out_shardings_for(i8, sharding, digest)
    if fmt is not None:
        return fmt
    if sharding is None:
        return None
    n_out = 1 + (1 if i8 else 0) + (1 if digest else 0)
    return sharding if n_out == 1 else (sharding,) * n_out


def _export_with_digest(final, doc_base, i16, ob_rows, ov_slots, i8,
                        has_props, digest: bool):
    """Export a final state, optionally appending the [D, 2] digest plane
    as the LAST output leaf (see ``split_export_digest``)."""
    with jax.named_scope("export"):
        ex = _export_state(final, doc_base, i16, ob_rows, ov_slots, i8,
                           props_rows=has_props)
    if not digest:
        return ex
    with jax.named_scope("digest"):
        dig = _doc_digests(final, doc_base)
    return ex + (dig,) if isinstance(ex, tuple) else (ex, dig)


def program_name(family: str, digest: bool, start: str = "") -> str:
    """The stable module name of a family's fold+export program
    (``jit_<name>`` on the device trace's XLA Modules line): the family,
    whether the digest plane rides along, and the start (``cold`` or
    ``warm``) where the family compiles the two apart.  Inside it the
    ``fold``, ``export`` and ``digest`` named scopes label the ops."""
    return (f"{family}_fold_export{'_digest' if digest else ''}"
            f"{'_' + start if start else ''}")


@functools.lru_cache(maxsize=None)
def _export_cold_fn(S: int, i16: bool, ob_rows: bool = True,
                    ov_slots: int = 1, i8: bool = False,
                    sequential: bool = False, has_props: bool = True,
                    out_sharding=None, digest: bool = False):
    """Compiled cold-start fold+export for one (S, width, layout) bucket,
    its output laid out for a line-rate fetch.  ``ob_rows`` doubles as
    the fold fact has_ob and ``ov_slots`` (``ov_slot_count``) gives
    has_ov and the overlap slots the cold state starts with: the export
    elides exactly the planes the fold provably never writes.  A chunk
    with at most one overlap slot compiles the program it compiled
    before overlap slots were counted.  ``out_sharding`` (a
    NamedSharding) builds the mesh-sharded variant of the same pipeline —
    ONE derivation point for single-chip and multi-chip exports.
    ``digest`` appends the per-doc state digest plane (delta download)."""
    ov_slots = int(ov_slots)
    fold = _fold_fn(sequential, ob_rows, has_props, ov_slots > 0)

    def f(ops, doc_base):
        with jax.named_scope("fold"):
            ops = _widen_ops(ops, doc_base)
            final = fold(_cold_start(ops, S, ov_slots), ops)
        return _export_with_digest(final, doc_base, i16, ob_rows, ov_slots,
                                   i8, has_props, digest)

    f.__name__ = f.__qualname__ = program_name("mergetree", digest, "cold")
    fmt = _export_out(i8, out_sharding, digest)
    return jax.jit(f, out_shardings=fmt) if fmt is not None else jax.jit(f)


@functools.lru_cache(maxsize=None)
def _export_warm_fn(i16: bool, ob_rows: bool = True, ov_slots: int = 1,
                    i8: bool = False, sequential: bool = False,
                    has_props: bool = True, out_sharding=None,
                    digest: bool = False):
    """Compiled warm-start (base state uploaded) fold+export; see
    ``_export_cold_fn`` for ``ov_slots``/``out_sharding``/``digest``.
    The uploaded state carries the ``remx_*`` planes of its slots."""
    ov_slots = int(ov_slots)
    fold = _fold_fn(sequential, ob_rows, has_props, ov_slots > 0)

    def f(state, ops, doc_base):
        with jax.named_scope("fold"):
            state = _widen_state(state, doc_base)
            ops = _widen_ops(ops, doc_base)
            final = fold(state, ops)
        return _export_with_digest(final, doc_base, i16, ob_rows, ov_slots,
                                   i8, has_props, digest)

    f.__name__ = f.__qualname__ = program_name("mergetree", digest, "warm")
    fmt = _export_out(i8, out_sharding, digest)
    return jax.jit(f, out_shardings=fmt) if fmt is not None else jax.jit(f)


def export_layout_rows(meta: dict) -> int:
    """Row count of the transfer buffer replay_export emits for this
    packed chunk's layout facts (elisions + byte packing)."""
    _i16, ob_rows, ov_slots, i8, props_rows = _export_flags(meta)
    fields = _export_fields(ob_rows, ov_slots)
    K = meta.get("props_K", 1) if props_rows else 0
    n_extra = len(ov_extra_fields(ov_slots))
    if i8:
        n_bytes = len(fields) - 1 + K + n_extra
        return 1 + (n_bytes + 1) // 2  # misc rides the separate output
    return len(fields) + K + n_extra + 1


def _export_flags(meta: dict):
    """The transfer-layout facts BOTH sides of the export handshake use
    (dispatch builds the buffer, extraction widens it) — one derivation
    point so they can never disagree.  The third fact is the chunk's
    overlap slot count (``ov_slot_count``)."""
    i16 = bool(meta.get("i16_ok"))
    return (
        i16,
        bool(meta.get("ob_rows", True)),
        int(meta.get("ov_slots", 1)),
        i16 and bool(meta.get("i8_ok")),
        bool(meta.get("has_props", True)),
    )


#: upload-side narrow dtypes (h2d transfer encoding — see
#: ``narrow_ops_for_upload``); per-field, chosen once so the jit cache
#: sees exactly two op-stream signatures (all-int32 or this).
_UPLOAD_NARROW_DTYPES = {
    "kind": np.int8, "client": np.int8,
    "seq": np.int16, "ref_seq": np.int16, "min_seq": np.int16,
    "a": np.int16, "b": np.int16, "tstart": np.int16, "tlen": np.int16,
    "pvals": np.int16,
}


def narrow_ops_for_upload(ops: MTOps, meta: dict) -> MTOps:
    """Narrow a packed op stream for the h2d link: int32 → int16 rows
    (int8 for kind/client), with insert ``tstart`` rebased per document
    (``tstart - doc_base[d]`` — a doc's arena spans are contiguous, the
    same transform the int16 EXPORT layout applies on the way down).
    The device widens in-graph (``_widen_ops``), so this is purely a
    transfer encoding: ~55% off the op-stream upload, the h2d leg of the
    link-bound pipeline (BASELINE.md round-5: with the fold at ~2 ms,
    e2e is host+link).

    Applies only when the chunk's ``i16_ok`` value-bound fact holds AND
    a direct bounds re-check of every field passes (belt and braces —
    any violation falls back to the wide upload, never corrupts);
    device-resident or already-narrow streams pass through unchanged.
    ``FF_UPLOAD_NARROW=0`` disables."""
    import os

    if (not meta.get("i16_ok")
            or not isinstance(ops.kind, np.ndarray)
            or ops.seq.dtype != np.int32
            or os.environ.get("FF_UPLOAD_NARROW", "1") == "0"):
        return ops
    doc_base = np.asarray(meta["doc_base"], np.int32)
    is_ins = ops.kind == K_INSERT
    # Non-insert rows must carry tstart == 0 (pack invariant; the fold
    # reads op tstart only under is_ins) for the rebase to round-trip.
    if int(np.abs(np.where(is_ins, 0, ops.tstart)).max(initial=0)) != 0:
        return ops
    rebased = np.where(is_ins, ops.tstart - doc_base[:, None], 0)
    narrow = {"tstart": rebased}
    for f in MTOps._fields:
        if f != "tstart":
            narrow[f] = getattr(ops, f)
    for f, dt in _UPLOAD_NARROW_DTYPES.items():
        info = np.iinfo(dt)
        v = narrow[f]
        if not (int(v.min(initial=0)) >= info.min
                and int(v.max(initial=0)) <= info.max):
            return ops  # bounds re-check failed → wide upload
    return MTOps(**{f: narrow[f].astype(_UPLOAD_NARROW_DTYPES[f])
                    for f in MTOps._fields})


def narrow_state_for_upload(state: MTState, meta: dict) -> MTState:
    """Narrow a warm chunk's base state for the h2d link — the catch-up
    service's snapshot+tail shape uploads 13 ``(D, S)`` int32 planes per
    chunk, the dominant upload for warm chunks.  int32 → int16 with the
    NOT_REMOVED sentinel remapped (the inverse the device applies is the
    same transform the i16 export layout already round-trips) and slot
    ``tstart`` rebased per doc for live slots (dead slots are zero by the
    pack invariant, re-checked here).  ``props`` (value ids ≥ -1) and
    ``n`` narrow unconditionally under the same bound; ``overflow`` stays
    bool.  Any bounds violation falls back to the wide upload."""
    import os

    if (not meta.get("i16_ok")
            or not isinstance(state.tstart, np.ndarray)
            or state.ins_seq.dtype != np.int32
            or os.environ.get("FF_UPLOAD_NARROW", "1") == "0"):
        return state
    doc_base = np.asarray(meta["doc_base"], np.int32)
    S = state.tstart.shape[1]
    live = np.arange(S, dtype=np.int32)[None, :] < state.n[:, None]
    if int(np.abs(np.where(live, 0, state.tstart)).max(initial=0)) != 0:
        return state  # dead slots must be zero for the rebase round trip
    info = np.iinfo(np.int16)

    def narrow16(v, sentinel_seq: bool):
        """``v`` as int16, or None where a value does not fit."""
        if sentinel_seq:
            # Real values must stay STRICTLY below the remapped sentinel
            # (I16_LIMIT, the same bound i16_ok is defined against) — a
            # genuine 32767 would widen back as NOT_REMOVED and
            # resurrect a removed segment.
            reals = np.where(v == NOT_REMOVED, 0, v)
            if int(reals.max(initial=0)) > I16_LIMIT:
                return None
            v = np.where(v == NOT_REMOVED, np.int32(I16_NOT_REMOVED), v)
        if not (info.min <= int(v.min(initial=0))
                and int(v.max(initial=0)) <= info.max):
            return None
        return v.astype(np.int16)

    narrow = {}
    for f in EXPORT_SLOT_FIELDS:  # the 12 slot planes, export's own list
        v = getattr(state, f)
        if f == "tstart":
            v = np.where(live, v - doc_base[:, None], 0)
        narrow[f] = narrow16(v, f in SENTINEL_SEQ_FIELDS)
    # The overlap slots past the first narrow like rem2.
    remx_seq = tuple(narrow16(v, True) for v in state.remx_seq)
    remx_client = tuple(narrow16(v, False) for v in state.remx_client)
    if any(v is None for v in
           list(narrow.values()) + list(remx_seq + remx_client)):
        return state
    if not (int(state.props.min(initial=0)) >= info.min
            and int(state.props.max(initial=0)) <= info.max
            and int(state.n.max(initial=0)) <= info.max):
        return state
    return MTState(
        **narrow,
        props=state.props.astype(np.int16),
        n=state.n.astype(np.int16),
        overflow=state.overflow,
        remx_seq=remx_seq,
        remx_client=remx_client,
    )


def _widen_state(state: MTState, doc_base: jnp.ndarray) -> MTState:
    """In-graph inverse of ``narrow_state_for_upload`` (identity on wide
    states); refuses unknown encodings loudly like ``_widen_ops``."""
    if state.ins_seq.dtype == jnp.int32:
        return state
    if state.ins_seq.dtype != jnp.int16:
        raise TypeError(
            f"state has ins_seq dtype {state.ins_seq.dtype}; expected "
            f"int32 (wide) or the int16 narrow_state_for_upload encoding"
        )
    w = {f: getattr(state, f).astype(jnp.int32)
         for f in EXPORT_SLOT_FIELDS}
    n = state.n.astype(jnp.int32)
    S = state.tstart.shape[1]
    live = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1) < n[:, None]
    w["tstart"] = jnp.where(live, w["tstart"] + doc_base[:, None], 0)
    def unmap(v):
        v = v.astype(jnp.int32)
        return jnp.where(v == int(I16_NOT_REMOVED), NOT_REMOVED, v)

    for f in SENTINEL_SEQ_FIELDS:
        w[f] = unmap(w[f])
    return MTState(**w, props=state.props.astype(jnp.int32), n=n,
                   overflow=state.overflow,
                   remx_seq=tuple(unmap(v) for v in state.remx_seq),
                   remx_client=tuple(v.astype(jnp.int32)
                                     for v in state.remx_client))


def _widen_ops(ops: MTOps, doc_base: jnp.ndarray) -> MTOps:
    """In-graph inverse of ``narrow_ops_for_upload`` (identity on wide
    streams): one fused cast per field plus the insert-tstart un-rebase.
    Runs first inside the jitted fold+export wrappers, so both upload
    widths share one jit entry (the cache keys on input avals).

    The un-rebase applies ONLY to the exact encoding the narrower emits
    (int16 seq rows) — any other non-int32 stream was never rebased, so
    silently 'widening' it would corrupt every insert's arena offset;
    refuse loudly instead."""
    if ops.seq.dtype == jnp.int32:
        return ops
    if ops.seq.dtype != jnp.int16:
        raise TypeError(
            f"op stream has seq dtype {ops.seq.dtype}; expected int32 "
            f"(wide) or the int16 narrow_ops_for_upload encoding"
        )
    w = {f: getattr(ops, f).astype(jnp.int32) for f in MTOps._fields}
    w["tstart"] = jnp.where(w["kind"] == K_INSERT,
                            w["tstart"] + doc_base[:, None], 0)
    return MTOps(**w)


def replay_export(state: Optional[MTState], ops: MTOps, meta: dict,
                  S: Optional[int] = None,
                  digest: bool = False,
                  doc_base: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Dispatch the fold+export for a packed chunk (async); the result is
    the fused export buffer handle, int16 when the chunk qualifies (with
    obliterate/overlap row elision and int8 pair-packing per the pack-time
    layout facts).  Pass ``state=None`` for all-cold chunks (initial state
    built in-graph — no zero upload).  ``digest=True`` additionally emits
    the per-doc state digest plane as the last output leaf (split it off
    with ``split_export_digest`` — the delta-download gate fetches ONLY
    that tiny plane eagerly).  ``doc_base`` (optional) supplies a
    DEVICE-RESIDENT per-doc arena base (the tier-2.5 resident tier keeps
    it on device so an exact warm hit uploads nothing); it must equal
    ``meta["doc_base"]`` — passing the real bases is inert on layouts
    that ignore them."""
    i16, ob_rows, ov_slots, i8, has_props = _export_flags(meta)
    # The digest rebases tstart per doc even on non-i16 chunks, so an
    # unchanged document digests identically across repacks that moved
    # its absolute arena offsets (_export_state reads doc_base only
    # under i16 — passing the real bases is inert for the buffer).
    if doc_base is None:
        doc_base = jnp.asarray(meta["doc_base"]) if (i16 or digest) else \
            jnp.zeros((ops.kind.shape[0],), jnp.int32)
    ops = narrow_ops_for_upload(ops, meta)  # h2d transfer encoding
    sequential = bool(meta.get("sequential"))
    if state is None:
        return _export_cold_fn(int(S), i16, ob_rows, ov_slots, i8,
                               sequential, has_props,
                               digest=digest)(ops, doc_base)
    state = narrow_state_for_upload(state, meta)
    return _export_warm_fn(i16, ob_rows, ov_slots, i8, sequential,
                           has_props,
                           digest=digest)(state, ops, doc_base)


def state_dict_from_export(export_np: np.ndarray,
                           ov_slots: int = 1) -> dict:
    """Adapt a downloaded export buffer (canonical layout of a chunk with
    ``ov_slots`` overlap slots) back to the state_np dict shape the
    extraction/interval code consumes (zero-copy row views;
    ``remx_seq``/``remx_client`` are ``[D, slots past the first, S]``)."""
    n_extra = len(ov_extra_fields(ov_slots))
    F = len(EXPORT_SLOT_FIELDS)
    K = export_np.shape[1] - F - n_extra - 1
    out = {
        f: export_np[:, i, :] for i, f in enumerate(EXPORT_SLOT_FIELDS)
    }
    out["props"] = np.moveaxis(export_np[:, F:F + K, :], 1, 2)
    out["remx_seq"] = export_np[:, F + K:F + K + n_extra:2, :]
    out["remx_client"] = export_np[:, F + K + 1:F + K + n_extra:2, :]
    misc = export_np[:, -1, :]
    out["n"] = misc[:, 0]
    out["overflow"] = misc[:, 1]
    out["live_len"] = misc[:, 2]
    return out


# ---------------------------------------------------------------------------
# Host side: packing and canonical summary extraction
# ---------------------------------------------------------------------------


@dataclass
class MergeTreeDocInput:
    """One document's catch-up work item: optional base summary + op tail."""

    doc_id: str
    ops: Sequence[SequencedMessage]   # sequence-op contents, ascending seq
    base_records: Optional[List[dict]] = None  # normalized summary body
    final_seq: int = 0    # head seq after the tail (for the summary header)
    final_msn: int = 0    # final minimumSequenceNumber
    base_seq: int = 0     # seq of the base summary (for oracle fallback)
    base_msn: int = 0     # minSeq of the base summary
    base_intervals: Optional[Dict[str, dict]] = None  # intervals blob content
    # Native fast path: the ops pre-encoded as the liboppack binary record
    # stream (ops/native_pack.py) + the encoder's doc-local intern tables
    # (client ids; property keys / values when the stream annotates).
    # Interval ops never ride the stream, nor does a document with
    # ``base_records`` (the pack refuses it).  When set, ``ops`` may be
    # empty (the stream is authoritative) — C++ fills this doc's arrays,
    # translating doc-local property ids into the batch-global spaces.
    binary_ops: Optional[bytes] = None
    binary_clients: Optional[Sequence[str]] = None
    binary_prop_keys: Optional[Sequence[str]] = None
    binary_values: Optional[Sequence[Any]] = None
    #: attribution-enabled document (SURVEY §1 layer 8): the summary gains
    #: an "attribution" blob of pre-clamp insert seqs per merged sub-run
    #: (byte-identical to SharedString.summarize with an attributor).  The
    #: export already carries pre-clamp ins_seq — clamping is host-side —
    #: so this is pure extraction work; such docs take the Python record
    #: path (the C++ extractor emits bodies only).
    attribution: bool = False
    #: Opaque identity of the (document, base summary, storage generation)
    #: this tail extends — set by callers (the catch-up service) that want
    #: the pipeline's pack cache to reuse packed windows across calls.
    #: The contract: two inputs with equal tokens draw their ops from the
    #: SAME append-only sequenced stream over the SAME base, so a shared
    #: (first_seq .. last_seq) prefix is byte-identical.  None (the
    #: default) opts the doc out of pack caching entirely.
    cache_token: Optional[tuple] = None


def prime_stream_counts(docs: Sequence[MergeTreeDocInput]) -> None:
    """Count the streams of ``docs`` not yet counted, in one native call
    (``count_streams``), and keep each document's counts on it."""
    todo = [d for d in docs if d.binary_ops is not None
            and getattr(d, "_stream_counts", None) is None]
    if todo:
        counts = count_streams([d.binary_ops for d in todo]).tolist()
        for doc, row in zip(todo, counts):
            doc._stream_counts = tuple(row)


def stream_counts(doc: MergeTreeDocInput) -> Tuple[int, int, int, int, int]:
    """``(n_ops, text_bytes, text_chars, first_seq, last_seq)`` of a stream
    document, computed once per document: the pack sizes its rows from
    it, and the cache tiers read the op window (count, first and last
    seq) from it."""
    counts = getattr(doc, "_stream_counts", None)
    if counts is None:
        prime_stream_counts([doc])
        counts = doc._stream_counts
    return counts


class _DocPack:
    """Per-document host bookkeeping during packing."""

    def __init__(self) -> None:
        self.clients = Interner()
        self.interval_ops: List[SequencedMessage] = []
        self.needs_fallback = False

    def client_idx(self, client_id) -> int:
        if client_id is None:
            return -1
        return self.clients.intern(client_id)


def fill_sequence_op_rows(op, d: int, t: int, msgs, pack, arena,
                          key_id, values) -> int:
    """Fill doc ``d``'s op rows from a message list, starting after row
    ``t`` — THE per-op row fill, shared by the fresh pack below and the
    pack cache's suffix extension (ops/pipeline.py) so the two can never
    drift byte-wise.  Interval ops route into ``pack.interval_ops``;
    ``key_id`` maps a property key to its chunk-global column.  Returns
    the last row filled."""
    for msg in msgs:
        contents = msg.contents
        kind = contents["kind"]
        if kind.startswith("interval"):
            for cl in ([msg.client_id] if msg.client_id else []):
                pack.client_idx(cl)
            pack.interval_ops.append(msg)
            continue
        t += 1
        op["seq"][d, t] = msg.seq
        op["client"][d, t] = pack.client_idx(msg.client_id)
        op["ref_seq"][d, t] = msg.ref_seq
        op["min_seq"][d, t] = msg.min_seq
        if kind == "insert":
            op["kind"][d, t] = K_INSERT
            op["a"][d, t] = contents["pos"]
            op["tstart"][d, t] = arena.append(contents["text"])
            op["tlen"][d, t] = len(contents["text"])
        elif kind == "remove":
            op["kind"][d, t] = K_REMOVE
            op["a"][d, t] = contents["start"]
            op["b"][d, t] = contents["end"]
        elif kind == "obliterate":
            op["kind"][d, t] = K_OBLITERATE
            op["a"][d, t] = contents["start"]
            op["b"][d, t] = contents["end"]
        elif kind == "annotate":
            op["kind"][d, t] = K_ANNOTATE
            op["a"][d, t] = contents["start"]
            op["b"][d, t] = contents["end"]
        else:
            raise ValueError(f"unknown sequence op kind {kind!r}")
        for key, value in (contents.get("props") or {}).items():
            op["pvals"][d, t, key_id(key)] = (
                PROP_ABSENT if value is None else values.intern(value)
            )
    return t


def pack_mergetree_batch(docs: Sequence[MergeTreeDocInput]):
    """Pack documents into uniform-shape device arrays + host metadata.

    Returns (state_arrays, op_arrays, meta) where meta carries everything
    needed to rebuild canonical summaries from the final device state.
    """
    prop_keys = Interner()
    values = Interner()
    arena = TextArena()
    doc_packs = [_DocPack() for _ in docs]

    # Pre-scan for the shared property-key vocabulary K.  Binary-stream
    # docs contribute their encoder-local key tables.
    for doc in docs:
        if doc.base_records:
            for rec in doc.base_records:
                for key in rec.get("p", {}):
                    prop_keys.intern(key)
        if doc.binary_ops is not None:
            for key in (doc.binary_prop_keys or []):
                prop_keys.intern(key)
            continue
        for msg in doc.ops:
            op = msg.contents
            if op["kind"].startswith("interval"):
                continue
            for key in (op.get("props") or {}):
                prop_keys.intern(key)
    # Power-of-two buckets: jitted shapes stay stable across batches instead
    # of recompiling the vmapped scan per (D, S, T, K).
    K = next_bucket(max(len(prop_keys), 1), floor=1)
    for d in docs:
        if d.binary_ops is not None:
            if d.base_records:
                # Base-record clients would shift the encoder's dense client
                # ids — a silent misattribution, so refuse (warm-start docs
                # take the message-list path).
                raise ValueError(
                    f"{d.doc_id}: binary_ops cannot be combined with "
                    f"base_records"
                )
    prime_stream_counts(docs)
    binary_counts = {i: d._stream_counts for i, d in enumerate(docs)
                     if d.binary_ops is not None}
    text_op_counts = [
        binary_counts[i][0] if i in binary_counts else
        sum(1 for m in d.ops if not m.contents["kind"].startswith("interval"))
        for i, d in enumerate(docs)
    ]
    # S and T use the finer bucket ladder: both are pure per-element costs
    # (T = scan length, S = export-transfer bytes — the pipeline bottleneck)
    # and neither needs to divide the mesh, so the extra shape variants buy
    # up to 25% less padding on the hot path.
    T = next_bucket_fine(max(text_op_counts, default=1), floor=16)
    base_counts = [len(d.base_records or []) for d in docs]
    S = max(
        (bc + 2 * t for bc, t in zip(base_counts, text_op_counts)), default=1
    )
    S = next_bucket_fine(max(S, 1), floor=32)

    D = len(docs)
    st = {
        "tstart": np.zeros((D, S), np.int32),
        "tlen": np.zeros((D, S), np.int32),
        "ins_seq": np.zeros((D, S), np.int32),
        "ins_client": np.full((D, S), -1, np.int32),
        "rem_seq": np.full((D, S), NOT_REMOVED, np.int32),
        "rem_client": np.full((D, S), -1, np.int32),
        "rem2_seq": np.full((D, S), NOT_REMOVED, np.int32),
        "rem2_client": np.full((D, S), -1, np.int32),
        "ob1_seq": np.full((D, S), NOT_REMOVED, np.int32),
        "ob1_client": np.full((D, S), -1, np.int32),
        "ob2_seq": np.full((D, S), NOT_REMOVED, np.int32),
        "ob2_client": np.full((D, S), -1, np.int32),
        "props": np.full((D, S, K), PROP_ABSENT, np.int32),
        "n": np.zeros((D,), np.int32),
        "overflow": np.zeros((D,), np.bool_),
    }
    op = {
        "kind": np.zeros((D, T), np.int32),
        "seq": np.zeros((D, T), np.int32),
        "client": np.zeros((D, T), np.int32),
        "ref_seq": np.zeros((D, T), np.int32),
        "min_seq": np.zeros((D, T), np.int32),
        "a": np.zeros((D, T), np.int32),
        "b": np.zeros((D, T), np.int32),
        "tstart": np.zeros((D, T), np.int32),
        "tlen": np.zeros((D, T), np.int32),
        "pvals": np.full((D, T, K), PROP_NOT_TOUCHED, np.int32),
    }

    doc_base = np.zeros((D,), np.int32)
    base_has_ob = False
    base_max_tlen = 0
    # Base records' overlap removers, written once the chunk's slot count
    # is known: (doc, slot, client ids), and per doc the most removers
    # (winner + "ro") any one record carries.
    base_ro = []
    base_removers = np.zeros((D,), np.int64)
    from .native_pack import pack_streams

    for d, doc in enumerate(docs):
        pack = doc_packs[d]
        if known_oracle_fallback(doc):
            # Docs routed here without the partition_replay pre-filter
            # still get the oracle (the docstring's pack-time parity).
            pack.needs_fallback = True
        if doc.binary_ops is not None:
            pack.clients = Interner.of(doc.binary_clients or ())
            if d and docs[d - 1].binary_ops is not None:
                continue  # packed with the run it belongs to
            # Native fast path: one C++ call fills the rows of this whole
            # run of stream docs, translating encoder-local property ids
            # into the batch-global intern spaces.
            end = d + 1
            while end < D and docs[end].binary_ops is not None:
                end += 1
            run = docs[d:end]
            text, chars = pack_streams(
                op, d, [r.binary_ops for r in run],
                [[prop_keys.intern(k) for k in r.binary_prop_keys or ()]
                 for r in run],
                [[values.intern(v) for v in r.binary_values or ()]
                 for r in run],
                len(arena), sum(binary_counts[i][1] for i in range(d, end)))
            doc_base[d:end] = len(arena) + chars[:-1]
            arena.append(text)
            continue
        doc_base[d] = len(arena)
        for s, rec in enumerate(doc.base_records or []):
            st["tstart"][d, s] = arena.append(rec["t"])
            st["tlen"][d, s] = len(rec["t"])
            st["ins_seq"][d, s] = rec["s"]
            st["ins_client"][d, s] = pack.client_idx(rec["c"])
            if "rs" in rec:
                st["rem_seq"][d, s] = rec["rs"]
                st["rem_client"][d, s] = pack.client_idx(rec.get("rc"))
            ob = rec.get("ob", [])
            if ob:
                base_has_ob = True
                st["ob1_seq"][d, s] = ob[0][0]
                st["ob1_client"][d, s] = pack.client_idx(ob[0][1])
                if len(ob) > 1:
                    st["ob2_seq"][d, s] = ob[1][0]
                    st["ob2_client"][d, s] = pack.client_idx(ob[1][1])
                if len(ob) > 2:
                    pack.needs_fallback = True  # device tracks two stamps
            base_max_tlen = max(base_max_tlen, len(rec["t"]))
            ro = rec.get("ro", [])
            if ro:
                base_ro.append((d, s, [pack.client_idx(c) for c in ro]))
            base_removers[d] = max(int(base_removers[d]),
                                   ("rs" in rec) + len(ro))
            for key, value in rec.get("p", {}).items():
                st["props"][d, s, prop_keys.intern(key)] = values.intern(value)
        st["n"][d] = len(doc.base_records or [])

        fill_sequence_op_rows(op, d, -1, doc.ops, pack, arena,
                              prop_keys.intern, values)

    # int16-export eligibility: every value the final state can hold must fit
    # strictly under the int16 sentinel (see the export layout comment).
    max_doc_chars = 0
    for d in range(D):
        end = doc_base[d + 1] if d + 1 < D else len(arena)
        max_doc_chars = max(max_doc_chars, int(end) - int(doc_base[d]))
    max_seq = max(
        int(op["seq"].max(initial=0)),
        max((d.final_seq for d in docs), default=0),
        max((d.base_seq for d in docs), default=0),
    )
    max_clients = max((len(p.clients) for p in doc_packs), default=0)
    i16_ok = (
        max_seq < I16_LIMIT
        and max_doc_chars < I16_LIMIT
        and S < I16_LIMIT
        and len(values) < I16_LIMIT
        and max_clients < I16_LIMIT
    )
    # int8 pair-packing eligibility: every byte-row value (seqs incl. the
    # remapped sentinel, client/prop ids, segment lengths) fits a signed
    # byte.  tstart/misc stay 16-bit, so only the byte rows bound this.
    real_ops = op["kind"] != K_NOOP
    max_tlen = max(int(op["tlen"].max(initial=0)), base_max_tlen)
    i8_ok = (
        i16_ok
        and max_seq < I8_LIMIT
        and max_tlen < I8_LIMIT
        and len(values) < I8_LIMIT
        and max_clients < I8_LIMIT
    )
    # A fully sequential chunk (every ref_seq == seq-1) never lets a
    # remover see an already-removed slot.
    sequential = not bool(
        (real_ops & (op["ref_seq"] != op["seq"] - 1)).any()
    )
    ov_slots = ov_slot_count(
        tail_remover_counts(op["kind"], op["client"]), base_removers,
        max((len(ids) for _d, _s, ids in base_ro), default=0), sequential)
    st["remx_seq"] = tuple(np.full((D, S), NOT_REMOVED, np.int32)
                           for _ in range(ov_slots - 1))
    st["remx_client"] = tuple(np.full((D, S), -1, np.int32)
                              for _ in range(ov_slots - 1))
    for d, s, ids in base_ro:
        # The base summary carries no overlap seqs, but any value below
        # the base seq is faithful (it sequenced before every tail op).
        if len(ids) > ov_slots:
            doc_packs[d].needs_fallback = True  # past the slot cap
            continue
        seq = docs[d].base_seq
        st["rem2_seq"][d, s], st["rem2_client"][d, s] = seq, ids[0]
        for j, c in enumerate(ids[1:]):
            st["remx_seq"][j][d, s], st["remx_client"][j][d, s] = seq, c
    meta = {
        "doc_packs": doc_packs,
        "prop_keys": list(prop_keys.values),
        "values": values,
        "arena": arena,
        "docs": docs,
        "doc_base": doc_base,
        "_S": S,  # the padded slot bucket (cold-start export builders)
        "i16_ok": i16_ok,
        "i8_ok": i8_ok,
        "props_K": K,
        # Export the 4 obliterate rows only when the chunk can touch them
        # (a pack-time fact: an obliterate op anywhere — including C++-
        # filled binary rows, which land in op["kind"] — or a base stamp).
        "ob_rows": base_has_ob or bool((op["kind"] == K_OBLITERATE).any()),
        # Overlap-remover slots (``ov_slot_count``): 0 elides the rem2
        # rows and keeps the planes constant in the fold.
        "ov_slots": ov_slots,
        # Props-free chunk (no annotate ops, no base props — the interner
        # saw no keys from ANY source): the plane stays constant, the
        # per-op plane shift traces away.
        "has_props": len(prop_keys) > 0,
        # Compile-time fold specialization (see _apply_op): base stamps
        # cannot exceed any sequential tail ref, so ``sequential`` alone
        # licenses the arrival-kill skip even on warm docs.
        "sequential": sequential,
    }
    return MTState(**st), MTOps(**op), meta


def _extract_records(meta, state_np: dict, d: int,
                     return_keys: bool = False):
    """Device state → the oracle's normalized record list (host side).

    ``return_keys=True`` additionally returns the ATTRIBUTION KEYS,
    mirroring ``MergeTreeOracle.normalized_records(return_keys=True)``:
    for each emitted record whose seq got clamped, the pre-clamp insert
    seqs of its merged sub-runs as ``[record_idx, [[chars, seq], ...]]``
    (the export's ins_seq column is pre-clamp — clamping happens here)."""
    doc = meta["docs"][d]
    pack = meta["doc_packs"][d]
    arena: TextArena = meta["arena"]
    prop_keys = meta["prop_keys"]
    values: Interner = meta["values"]
    msn = doc.final_msn
    records: List[dict] = []
    run_keys: List[Optional[list]] = []
    n = int(state_np["n"][d])
    for s in range(n):
        rs = int(state_np["rem_seq"][d, s])
        removed = rs != NOT_REMOVED
        stamps = []
        for o in ("ob1", "ob2"):
            o_s = int(state_np[f"{o}_seq"][d, s])
            if o_s != NOT_REMOVED and o_s > msn:
                oc = int(state_np[f"{o}_client"][d, s])
                stamps.append([o_s, pack.clients.lookup(oc)])
        if removed and rs <= msn \
                and int(state_np["ins_seq"][d, s]) <= msn and not stamps:
            continue  # expired tombstone (active stamps pin it)
        ins_seq = int(state_np["ins_seq"][d, s])
        ins_client = int(state_np["ins_client"][d, s])
        if ins_seq <= msn:
            seq_out, client_out = 0, None
        else:
            seq_out = ins_seq
            client_out = pack.clients.lookup(ins_client)
        rec = {
            "t": arena.slice(
                int(state_np["tstart"][d, s]), int(state_np["tlen"][d, s])
            ),
            "s": seq_out,
            "c": client_out,
        }
        if removed:
            rec["rs"] = rs
            rc = int(state_np["rem_client"][d, s])
            rec["rc"] = pack.clients.lookup(rc) if rc >= 0 else None
        if stamps:
            rec["ob"] = stamps
        ro = [int(state_np["rem2_client"][d, s])]
        ro += [int(c) for c in state_np["remx_client"][d, :, s]]
        ro = sorted(pack.clients.lookup(c) for c in ro if c >= 0)
        if ro:
            rec["ro"] = ro
        props = {}
        for k, key in enumerate(prop_keys):
            vid = int(state_np["props"][d, s, k])
            if vid != PROP_ABSENT:
                props[key] = values.lookup(vid)
        if props:
            rec["p"] = dict(sorted(props.items()))
        if records:
            prev = records[-1]
            if (
                prev["s"] == rec["s"]
                and prev["c"] == rec["c"]
                and prev.get("rs") == rec.get("rs")
                and prev.get("rc") == rec.get("rc")
                and prev.get("ob") == rec.get("ob")
                and prev.get("ro") == rec.get("ro")
                and prev.get("p") == rec.get("p")
            ):
                prev["t"] += rec["t"]
                runs = run_keys[-1]
                if runs is not None:
                    if runs[-1][1] == ins_seq:
                        runs[-1][0] += len(rec["t"])  # same author run
                    else:
                        runs.append([len(rec["t"]), ins_seq])
                continue
        records.append(rec)
        run_keys.append(
            [[len(rec["t"]), ins_seq]] if rec["s"] == 0 else None
        )
    if not return_keys:
        return records
    keys = [
        [i, runs] for i, runs in enumerate(run_keys)
        if runs is not None and any(seq for _chars, seq in runs)
    ]
    return records, keys


def known_oracle_fallback(doc: MergeTreeDocInput) -> bool:
    # Memoized per doc object: partition_replay pre-filters with this and
    # pack-time parity re-checks it — the op/binary scans must not run
    # twice on the packing hot path (review-found).
    cached = getattr(doc, "_fallback_verdict", None)
    if cached is not None:
        return cached
    verdict = _known_oracle_fallback_uncached(doc)
    doc._fallback_verdict = verdict
    return verdict


def _known_oracle_fallback_uncached(doc: MergeTreeDocInput) -> bool:
    """True when a doc is known *before packing* to need the oracle path:
    more overlap removers on a base record than the device has slots
    (``OV_SLOT_CAP``), >2 obliterate stamps on a base record (two device
    stamp slots), or interval ops mixed with obliterate ops
    (reference-slide timing over obliterated segments is host-folded only
    through the oracle).  Pack-time's ``needs_fallback`` applies the same
    rules; filtering first keeps such docs from inflating the shared
    power-of-two buckets."""
    for r in doc.base_records or []:
        if len(r.get("ro", [])) > OV_SLOT_CAP or len(r.get("ob", [])) > 2:
            return True
    has_interval = doc.base_intervals is not None
    has_obl = False
    for msg in doc.ops:
        kind = msg.contents.get("kind", "")
        if kind.startswith("interval"):
            has_interval = True
        elif kind == "obliterate":
            has_obl = True
    if doc.binary_ops is not None and has_interval and not has_obl:
        from .native_pack import binary_has_obliterate

        has_obl = binary_has_obliterate(doc.binary_ops)
    if has_obl and has_interval:
        return True
    return False


def oracle_fallback_summary(doc: MergeTreeDocInput) -> SummaryTree:
    """Full oracle replay of one document — the exactness escape hatch for
    the rare shapes the device path flags (more concurrent removers of one
    segment than the chunk's overlap slots, or a base record with more
    overlap removers than the slot cap)."""
    from ..dds.sequence import SharedString

    replica = SharedString(doc.doc_id)
    if doc.attribution:
        # Attribution-enabled docs must emit their keys blob on fallback
        # too (summarize keys on the flag alone; table reads are container
        # state, not needed here).
        from ..runtime.attributor import Attributor

        replica._attributor = Attributor()
    if doc.base_records is not None:
        replica.tree.load_records(doc.base_records, doc.base_seq, doc.base_msn)
        for label, obj in (doc.base_intervals or {}).items():
            replica.get_interval_collection(label).load_obj(obj)
    ops = doc.ops
    if doc.binary_ops is not None and not ops:
        from .native_pack import decode_string_ops

        ops = decode_string_ops(doc.binary_ops,
                                list(doc.binary_clients or []),
                                prop_keys=doc.binary_prop_keys,
                                values=doc.binary_values)
    for msg in ops:
        replica.process(msg, local=False)
    replica.advance(doc.final_seq, doc.final_msn)
    return replica.summarize()


def summaries_from_export(meta, export_np: np.ndarray,
                          stats: Optional[dict] = None,
                          stage: Optional[dict] = None) -> List[SummaryTree]:
    """Canonical summaries for a whole chunk from the fused export buffer.

    Bodies come from the C++ extractor (one pass over the buffer) when
    liboppack is available, else the per-slot Python extraction; interval
    blobs and oracle-fallback docs take the host paths either way.
    ``stats`` (optional dict) accumulates ``device_docs`` /
    ``fallback_docs`` counters — the true device-vs-oracle split, with
    the reason of each fallback (``fallback_pack``: flagged at pack time;
    ``fallback_overflow``: a segment had more concurrent removers than
    the chunk's overlap slots) — and ``ov_slots_<n>``, the chunks folded
    with n overlap slots; ``stage`` (optional dict) the seconds of the
    chunk's oracle folds under ``fallback``."""
    from .batching import count_fallback
    from .interval_replay import FinalStateView, replay_intervals
    from .native_pack import extract_bodies

    docs = meta["docs"]
    D = len(docs)
    _i16, ob_rows_f, ov_slots, i8_f, props_rows_f = _export_flags(meta)
    widened = widen_export_native(
        export_np, meta.get("doc_base"), ob_rows_f, ov_slots, i8_f,
        meta.get("props_K"), props_rows_f)
    export_np = widened if widened is not None else widen_export(
        export_np, meta.get("doc_base"),
        ob_rows=ob_rows_f, ov_slots=ov_slots,
        i8=i8_f, n_props=meta.get("props_K"),
        props_rows=props_rows_f)
    state_np = state_dict_from_export(export_np, ov_slots)
    skip = np.zeros(D, np.uint8)
    for d in range(D):
        if meta["doc_packs"][d].needs_fallback:
            skip[d] = 1
            count_fallback(stats, "pack")
        elif state_np["overflow"][d]:
            skip[d] = 1
            count_fallback(stats, "overflow")
    if stats is not None:
        n_skip = int(skip.sum())
        stats["device_docs"] = stats.get("device_docs", 0) + D - n_skip
        key = f"ov_slots_{ov_slots}"
        stats[key] = stats.get(key, 0) + 1
    msn = np.asarray([doc.final_msn for doc in docs], np.int32)
    arena_text = meta["arena"].finalize()
    # Attribution docs take the Python record path below (their key blob
    # needs the pre-clamp seqs alongside the merge boundaries), so the
    # C++ pass must not extract their bodies just to discard them —
    # body_skip extends the fallback skip WITHOUT polluting the stats.
    body_skip = skip.copy()
    for d in range(D):
        if docs[d].attribution:
            body_skip[d] = 1
    bodies = extract_bodies(
        np.ascontiguousarray(export_np, np.int32), arena_text,
        [list(meta["doc_packs"][d].clients.values) for d in range(D)],
        meta["prop_keys"], list(meta["values"].values),
        msn, body_skip, int(NOT_REMOVED),
        ov_extra=max(ov_slots - 1, 0),
    )
    out: List[Optional[SummaryTree]] = []
    live_len = state_np["live_len"]
    for d, doc in enumerate(docs):
        pack = meta["doc_packs"][d]
        if skip[d]:
            out.append(None)  # the oracle's, below
            continue
        tree = SummaryTree()
        # Byte-equal to canonical_json({...}) (keys pre-sorted, minimal
        # separators) — pinned by test_header_fast_format; json.dumps per
        # doc was ~20% of chunk extraction.
        tree.add_blob(
            "header",
            b'{"length":%d,"minSeq":%d,"seq":%d}'
            % (int(live_len[d]), doc.final_msn, doc.final_seq),
        )
        if doc.attribution:
            # Attribution docs take the Python record path (pinned
            # bit-identical to the C++ bodies): the keys blob needs the
            # pre-clamp seqs alongside the merge boundaries.
            records, keys = _extract_records(meta, state_np, d,
                                             return_keys=True)
            tree.add_blob("body", canonical_json(records))
            if keys:
                tree.add_blob("attribution", canonical_json(keys))
        elif bodies is not None:
            tree.add_blob("body", bodies[d])
        else:
            tree.add_blob(
                "body", canonical_json(_extract_records(meta, state_np, d))
            )
        if pack.interval_ops or doc.base_intervals:
            view = FinalStateView(state_np, d, int(NOT_REMOVED))
            intervals = replay_intervals(
                view,
                pack.interval_ops,
                pack.client_idx,
                base_intervals=doc.base_intervals,
                base_seq=doc.base_seq,
            )
            if intervals:
                tree.add_blob("intervals", canonical_json(intervals))
        out.append(tree)
    skipped = np.flatnonzero(skip)
    if len(skipped):
        with span("pipeline.fallback", stage, "fallback",
                  docs=len(skipped)):
            for d in skipped:
                out[d] = oracle_fallback_summary(docs[d])
    return out


def replay_mergetree_batch(
    docs: Sequence[MergeTreeDocInput],
    stats: Optional[dict] = None,
) -> List[SummaryTree]:
    """Full pipeline: pack → vmapped device op-fold → fused export download
    → canonical summaries.

    Byte-identical to ``SharedString.summarize()`` after the oracle replays
    the same log (asserted by tests/test_mergetree_kernel.py).
    ``stats`` accumulates ``device_docs`` / ``fallback_docs`` (pre-pack
    routing + post-fold overflow fallbacks).
    """
    from .batching import partition_replay

    def fold_batch(batch):
        state, ops, meta = pack_mergetree_batch(batch)
        if not any(d.base_records for d in batch):
            # all-cold chunk: initial state is built in-graph (no zero
            # upload; the host link is the bottleneck, not the fold)
            export = replay_export(None, ops, meta, S=state.tstart.shape[1])
        else:
            export = replay_export(state, ops, meta)
        return summaries_from_export(meta, export_to_numpy(export),
                                     stats=stats)

    return partition_replay(
        docs, known_oracle_fallback, oracle_fallback_summary, fold_batch,
        stats=stats,
    )
