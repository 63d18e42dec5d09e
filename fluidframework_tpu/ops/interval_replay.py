"""Interval-op folding over final device merge-tree state (host side).

Interval ops (add/change/delete) are rare relative to text ops, so the device
folds only the text ops; this module folds the interval ops afterwards *over
the final device state*.  That is possible because the device keeps every
tombstone: any historical view is reconstructible from the final arrays —

- bounded visibility at fold position ``s`` for client ``c``:
  insert counts iff ``ins_seq <= ref`` or (own and ``ins_seq < s``); removal
  counts iff ``rem_seq <= ref`` or the client is a remover whose removal
  sequenced before ``s`` (the overlap-remover slots carry exact overlap
  timing — the reason the kernel tracks (seq, client) pairs, not a bitmask);
- reference slides replay lazily as a cascade: a ref attached at ``s`` on a
  segment removed at ``t >= s`` slides at ``t`` to the nearest segment that
  was sequenced-alive *at t* (``ins_seq < t`` and not removed before ``t``),
  repeating while the landing segment is itself removed later.  This
  reproduces the oracle's eager slide-on-remove event order exactly.

The output is the same canonical intervals blob ``SharedString.summarize()``
emits; byte-identity vs the oracle is asserted by tests.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..protocol.messages import SequencedMessage

NO_CLIENT_IDX = -2  # matches no per-doc client index


class FinalStateView:
    """Historical-view resolution over one document's final segment arrays."""

    def __init__(self, state_np: dict, d: int, not_removed: int) -> None:
        n = int(state_np["n"][d])
        self.n = n
        self.tlen = np.asarray(state_np["tlen"][d, :n])
        self.ins_seq = np.asarray(state_np["ins_seq"][d, :n])
        self.ins_client = np.asarray(state_np["ins_client"][d, :n])
        self.rem_seq = np.asarray(state_np["rem_seq"][d, :n])
        self.rem_client = np.asarray(state_np["rem_client"][d, :n])
        def slots(first, more):
            """One row per overlap slot: rem2, then any further slots
            (``remx_seq``/``remx_client``, absent from states without
            them)."""
            rows = np.asarray(state_np[first][d, :n])[None]
            if more not in state_np:
                return rows
            return np.concatenate([rows,
                                   np.asarray(state_np[more][d, :, :n])])

        self.ov_seq = slots("rem2_seq", "remx_seq")
        self.ov_client = slots("rem2_client", "remx_client")
        self.ob1_seq = np.asarray(state_np["ob1_seq"][d, :n])
        self.ob1_client = np.asarray(state_np["ob1_client"][d, :n])
        self.ob2_seq = np.asarray(state_np["ob2_seq"][d, :n])
        self.ob2_client = np.asarray(state_np["ob2_client"][d, :n])
        self.not_removed = not_removed
        self._vis_cache: Dict[tuple, np.ndarray] = {}

    # -- bounded historical views ---------------------------------------------

    def _vis_cumsum(self, ref: int, client: int, up_to: int) -> np.ndarray:
        """Inclusive cumsum of per-slot visible lengths for one bounded
        view.  A slot is visible iff its insert sequenced at or below
        ``ref`` (or is the client's own, earlier in the fold) AND no
        removal counts against the view: a removal sequenced at or below
        ``ref``, or the client's own first/second removal earlier in the
        fold (NOT_REMOVED is int32-max, so the < / <= comparisons short
        out identically to the scalar rules).  Tiny FIFO cache (2
        entries): every realizable hit is either the base view resolved
        repeatedly up front or one op's start/end pair back-to-back —
        each interval op's (ref, client, seq) key is unique, so an
        unbounded cache would retain one O(n) array per op for the
        lifetime of the extraction."""
        key = (ref, client, up_to)
        hit = self._vis_cache.get(key)
        if hit is not None:
            return hit
        if len(self._vis_cache) >= 2:
            self._vis_cache.pop(next(iter(self._vis_cache)))
        ins_vis = (self.ins_seq <= ref) | (
            (self.ins_client == client) & (self.ins_seq < up_to)
        )
        is_removed = self.rem_seq != self.not_removed
        removed = (
            (is_removed & (self.rem_seq <= ref))
            | ((self.rem_client == client) & (self.rem_seq < up_to))
            | ((self.ov_client == client) & (self.ov_seq < up_to)).any(0)
            # Ob-stamp authors are involved in the removal (the oracle's
            # rule; kernel-side gap found at fuzz seed 1500041) — the
            # stamp itself must be sequenced before the view's fold
            # position, as must the removal.
            | (is_removed & (self.rem_seq < up_to)
               & (((self.ob1_client == client) & (self.ob1_seq < up_to))
                  | ((self.ob2_client == client) & (self.ob2_seq < up_to))))
        )
        cum = np.cumsum(np.where(ins_vis & ~removed, self.tlen, 0))
        self._vis_cache[key] = cum
        return cum

    def resolve(self, pos: int, ref: int, client: int, up_to: int):
        """View-position → (slot, offset) anchor, or None (empty view).
        Mirrors MergeTreeOracle.create_reference.  Vectorized: one
        visibility cumsum + searchsorted instead of a per-slot Python
        walk (the interval fold's hot loop — config #3)."""
        if self.n == 0:
            return None
        cum = self._vis_cumsum(ref, client, up_to)
        total = int(cum[-1])
        if pos < total:
            s = int(np.searchsorted(cum, pos, side="right"))
            return s, pos - int(cum[s - 1] if s else 0)
        if total == 0:
            return None  # empty view — nothing to anchor to
        # Past the end: anchor at the END of the LAST visible slot — the
        # first index where cum reaches total (contributions are
        # positive, so that index is the last contributor).
        s = int(np.searchsorted(cum, total - 1, side="right"))
        return s, int(self.tlen[s])

    # -- slide cascade ---------------------------------------------------------

    def _valid_at(self, s: int, t: int) -> bool:
        if self.ins_seq[s] >= t:
            return False  # not sequenced-inserted yet at t
        return self.rem_seq[s] == self.not_removed or self.rem_seq[s] > t

    def anchor_final(self, slot: int, offset: int, attach_seq: int):
        """Replay the slide cascade for a ref attached at fold position
        ``attach_seq``; returns the final (slot, offset) or None (detached)."""
        s = attach_seq
        while slot is not None and self.rem_seq[slot] != self.not_removed:
            t = max(s, int(self.rem_seq[slot]))
            target = None
            for j in range(slot + 1, self.n):
                if self._valid_at(j, t):
                    target, offset = j, 0
                    break
            if target is None:
                for j in range(slot - 1, -1, -1):
                    if self._valid_at(j, t):
                        target, offset = j, int(self.tlen[j])
                        break
            if target is None:
                return None
            slot, s = target, t
        return slot, offset

    def position(self, anchor) -> int:
        """Final sequenced-view position of an anchor (None → 0)."""
        if anchor is None:
            return 0
        slot, offset = anchor
        pos = int(
            np.sum(
                np.where(self.rem_seq[:slot] == self.not_removed,
                         self.tlen[:slot], 0)
            )
        )
        if self.rem_seq[slot] == self.not_removed:
            pos += min(offset, int(self.tlen[slot]))
        return pos


def replay_intervals(
    view: FinalStateView,
    interval_ops: Sequence[SequencedMessage],
    client_index,  # callable client_id -> per-doc idx
    base_intervals: Optional[Dict[str, dict]] = None,
    base_seq: int = 0,
) -> Dict[str, dict]:
    """Fold interval ops over the final state; returns {label: summary_obj}
    byte-compatible with IntervalCollection.summary_obj()."""
    # label -> id -> (start_ref, end_ref, props) with ref = (slot, off, seq)
    collections: Dict[str, Dict[str, list]] = {}
    for label, obj in (base_intervals or {}).items():
        coll = collections.setdefault(label, {})
        for interval_id, rec in obj.items():
            start = view.resolve(rec["start"], base_seq, NO_CLIENT_IDX, base_seq + 1)
            end = view.resolve(rec["end"], base_seq, NO_CLIENT_IDX, base_seq + 1)
            coll[interval_id] = [
                (*start, base_seq) if start else None,
                (*end, base_seq) if end else None,
                dict(rec.get("props") or {}),
            ]
    for msg in interval_ops:
        op = msg.contents
        label = op.get("label", "default")
        coll = collections.setdefault(label, {})
        interval_id = op["id"]
        kind = op["kind"]
        client = client_index(msg.client_id)

        def res(pos):
            a = view.resolve(pos, msg.ref_seq, client, msg.seq)
            return (*a, msg.seq) if a is not None else None

        if kind == "intervalAdd":
            props = {
                k: v for k, v in (op.get("props") or {}).items()
                if v is not None
            }
            coll[interval_id] = [res(op["start"]), res(op["end"]), props]
        elif kind == "intervalChange":
            iv = coll.get(interval_id)
            if iv is None:
                continue
            if op.get("start") is not None:
                iv[0] = res(op["start"])
            if op.get("end") is not None:
                iv[1] = res(op["end"])
            for key, value in (op.get("props") or {}).items():
                if value is None:
                    iv[2].pop(key, None)
                else:
                    iv[2][key] = value
        elif kind == "intervalDelete":
            coll.pop(interval_id, None)
        else:
            raise ValueError(f"unknown interval op kind {kind!r}")

    out: Dict[str, dict] = {}
    for label in sorted(collections):
        if not collections[label]:
            continue
        obj = {}
        for interval_id in sorted(collections[label]):
            start_ref, end_ref, props = collections[label][interval_id]
            rec: Dict[str, Any] = {
                "start": view.position(
                    view.anchor_final(*start_ref) if start_ref else None
                ),
                "end": view.position(
                    view.anchor_final(*end_ref) if end_ref else None
                ),
            }
            if props:
                rec["props"] = dict(sorted(props.items()))
            obj[interval_id] = rec
        if obj:
            out[label] = obj
    return out
