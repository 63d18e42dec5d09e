"""Pallas TPU kernel for the merge-tree op-fold (SURVEY §7 hard-part #4).

The XLA ``lax.scan`` fold streams the whole carried state — 12 int32
``[S]`` columns plus an ``[S, K]`` props plane per document — through HBM
on every op step: ~``2 * S * (12+K) * 4`` bytes per applied op, the
roofline bench.py reports against.  A document's entire state is tiny
(S=256, K=1: ~13 KB), so the TPU-native shape is a kernel instance that
loads state into VMEM once, folds every op of the tail with a
``fori_loop``, and writes the final state back once: HBM traffic drops
from O(T x state) to O(state + ops) and the fold leaves the bandwidth
roofline entirely.

Every block is 2-D and satisfies Mosaic's divisibility rule OUTRIGHT
(second-to-last dim a multiple of 8, last dim a multiple of 128):

- each grid step owns a SUBLANE-PACKED BATCH of B=8 documents, so the
  sublane dim is exactly 8 (the round-5 compile failure was a ``(1, S)``
  block; the recorded round-5 TPU error was its lane-dim sibling —
  ``block shape (1, 96)`` vs array ``(1024, 96)``);
- the lane dims pad to multiples of 128: ``S → Sp`` and ``T → Tp``
  round up, scalars (``n``/``overflow``) ride a 128-lane column with the
  value in lane 0.  Pad lanes are masked by construction — state lanes
  at ``slot >= n`` are inactive in every predicate, and the op loop runs
  only the REAL ``T`` steps (the pad rows are never read);
- the ``[S, K]`` props plane and ``[T, K]`` pvals plane are carried as K
  separate ``(8, lanes)`` planes (K is a static pack-time bucket), so no
  3-D block ever reaches Mosaic.

``D`` pads to a multiple of 8 with inert no-op documents.

Semantics are a faithful port of ``mergetree_kernel._apply_op`` /
``_split_at`` (the canonical scan step), restated Mosaic-conservatively
and batch-wide:

- every gather is a roll+select (the step's shifts are shift-right-by-one
  above an index) or a masked one-hot reduction (single-slot reads),
  reduced per-row (``axis=1, keepdims=True``);
- prefix sums are an unrolled Hillis-Steele ladder of masked rolls;
- first/nearest-slot searches are per-row min/max reductions over masked
  iotas;
- all iotas are 2D (``broadcasted_iota``); per-op values are ``(B, 1)``
  columns broadcasting against the ``(B, S)`` state planes.

Exact-parity tests (tests/test_pallas_fold.py) pin this port to the
canonical step on directed + fuzz streams, byte-identical through the
summary extraction, including shapes whose natural buckets violate the
divisibility rule (S=48, T=24, K=1) so the padding really executes.  CI
runs the kernel in interpret mode (pure jax, any backend); on real TPU
the compiled path is gated behind ``FF_PALLAS_FOLD=1``, and Mosaic
refuses it today ("cannot statically prove that index in dimension 1 is
a multiple of 128" — pinned by a strict xfail in
tests/test_v5e_compile.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .mergetree_kernel import (
    K_ANNOTATE,
    K_INSERT,
    K_OBLITERATE,
    K_REMOVE,
    MTOps,
    MTState,
    NOT_REMOVED,
    PROP_ABSENT,
    PROP_NOT_TOUCHED,
)

_OP_FIELDS = ("kind", "seq", "client", "ref_seq", "min_seq", "a", "b",
              "tstart", "tlen")
_COL_FIELDS = ("tstart", "tlen", "ins_seq", "ins_client", "rem_seq",
               "rem_client", "rem2_seq", "rem2_client", "ob1_seq",
               "ob1_client", "ob2_seq", "ob2_client")

#: documents per grid step — the int32 sublane count; blocks are (8, lanes)
DOC_BLOCK = 8
#: every block's lane dim is a multiple of this (Mosaic's (8, 128) rule)
LANE = 128

# Host-side pad fills, precomputed ONCE at import as plain Python ints —
# the typed helper that keeps the traced entry point free of int()
# concretization (fluidlint FL-TRACE-HOSTSYNC: int() on a module constant
# is concrete at trace time, but the rule cannot see through the binding;
# hoisting the conversion out of trace scope makes the code and the rule
# agree).
_NOT_REMOVED_FILL: int = int(NOT_REMOVED)
_PROP_ABSENT_FILL: int = int(PROP_ABSENT)
_PROP_NOT_TOUCHED_FILL: int = int(PROP_NOT_TOUCHED)


def _state_pad_fill(field: str) -> int:
    """Pad fill for a state plane: the NOT_REMOVED sentinel for removal /
    obliterate stamp seqs (a zero would read as 'removed at seq 0'),
    zero elsewhere — pad slots are inactive (``slot >= n``) in every
    predicate regardless; the sentinel is belt and braces."""
    if field.endswith("_seq") and field != "ins_seq":
        return _NOT_REMOVED_FILL
    return 0


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def _padded_dims(D: int, S: int, T: int):
    """The Mosaic-compliant padded shape: documents to a multiple of the
    8-row sublane batch, both lane dims (slots, op rows) to multiples of
    128 — so every BlockSpec below satisfies the (8, 128) divisibility
    rule by construction."""
    return _round_up(max(D, 1), DOC_BLOCK), _round_up(max(S, 1), LANE), \
        _round_up(max(T, 1), LANE)


def _iota(S: int) -> jnp.ndarray:
    return jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)


def _excl_cumsum(v: jnp.ndarray, S: int) -> jnp.ndarray:
    """Exclusive prefix sum over lanes as a Hillis-Steele ladder of
    masked rolls (statically unrolled; no native cumsum needed)."""
    slot = _iota(S)
    x = v
    d = 1
    while d < S:
        x = x + jnp.where(slot >= d, jnp.roll(x, d, axis=1), 0)
        d *= 2
    return x - v


def _at(f: jnp.ndarray, slot: jnp.ndarray, idx, valid, default):
    """Per-row f[idx] as a masked one-hot reduction (no gather): exact
    when ``valid`` (idx names a real slot), ``default`` otherwise.
    ``f`` is (B, S); ``idx``/``valid`` are (B, 1); result is (B, 1)."""
    hit = jnp.sum(jnp.where(slot == idx, f, 0), axis=1, keepdims=True)
    return jnp.where(valid, hit, jnp.int32(default))


def _shift_up_from(f: jnp.ndarray, slot: jnp.ndarray, idx) -> jnp.ndarray:
    """moved[i] = f[i] for i <= idx else f[i-1] — the pool shift-right a
    split/insert performs, as roll+select (per row; idx is (B, 1))."""
    return jnp.where(slot <= idx, f, jnp.roll(f, 1, axis=1))


def _visible(cols: dict, n, ref_seq, client, S: int) -> jnp.ndarray:
    slot = _iota(S)
    active = slot < n
    ins_vis = (cols["ins_seq"] <= ref_seq) | (cols["ins_client"] == client)
    removed = cols["rem_seq"] != NOT_REMOVED
    rem_vis = (
        (cols["rem_seq"] <= ref_seq)
        | (cols["rem_client"] == client)
        | (cols["rem2_client"] == client)
        # Ob-stamp authors are involved in the removal (the oracle's
        # fuzz-found rule; kernel gap found at fuzz seed 1500041).
        | (removed & (cols["ob1_client"] == client))
        | (removed & (cols["ob2_client"] == client))
    )
    return jnp.where(active & ins_vis & ~rem_vis, cols["tlen"], 0)


def _split_at(cols, props, n, char_pos, ref_seq, client, enable, S):
    """Port of mergetree_kernel._split_at on (B, S) rows; per-op values
    are (B, 1) columns; ``props`` is a tuple of K (B, S) planes."""
    slot = _iota(S)
    v = _visible(cols, n, ref_seq, client, S)
    cum = _excl_cumsum(v, S)
    inside = (cum < char_pos) & (char_pos < cum + v)
    first = jnp.min(jnp.where(inside, slot, S), axis=1, keepdims=True)
    do = enable & (first < S)
    idx = first  # unique when present; gated by ``do`` below
    off = char_pos - _at(cum, slot, idx, do, 0)

    new_cols = {f: _shift_up_from(cols[f], slot, idx) for f in _COL_FIELDS}
    is_left = slot == idx
    is_right = slot == idx + 1
    tlen = new_cols["tlen"]
    new_cols["tlen"] = jnp.where(
        is_left, off, jnp.where(is_right, tlen - off, tlen))
    new_cols["tstart"] = jnp.where(
        is_right, new_cols["tstart"] + off, new_cols["tstart"])

    cols = {f: jnp.where(do, new_cols[f], cols[f]) for f in _COL_FIELDS}
    props = tuple(
        jnp.where(do, _shift_up_from(p, slot, idx), p) for p in props
    )
    n = jnp.where(do, n + 1, n)
    return cols, props, n


def _apply_op_rows(cols, props, n, overflow, op, pvals, S, K):
    """Port of mergetree_kernel._apply_op on (B, S) planes.
    ``op`` is a dict of (B, 1) per-doc values; ``pvals`` is a tuple of K
    (B, 1) columns; ``props`` a tuple of K (B, S) planes;
    ``n``/``overflow`` are (B, 1)."""
    ref_seq, client = op["ref_seq"], op["client"]
    is_ins = op["kind"] == K_INSERT
    is_rem = op["kind"] == K_REMOVE
    is_ann = op["kind"] == K_ANNOTATE
    is_obl = op["kind"] == K_OBLITERATE
    is_rangey = is_rem | is_ann | is_obl

    cols, props, n = _split_at(cols, props, n, op["a"], ref_seq, client,
                               is_ins | is_rangey, S)
    cols, props, n = _split_at(cols, props, n, op["b"], ref_seq, client,
                               is_rangey, S)

    v = _visible(cols, n, ref_seq, client, S)
    cum = _excl_cumsum(v, S)
    slot = _iota(S)
    active = slot < n
    msn = op["min_seq"]
    ob1_live = (cols["ob1_seq"] != NOT_REMOVED) & (cols["ob1_seq"] > msn)
    ob2_live = (cols["ob2_seq"] != NOT_REMOVED) & (cols["ob2_seq"] > msn)
    expired = (
        (cols["rem_seq"] != NOT_REMOVED) & (cols["rem_seq"] <= msn)
        & (cols["ins_seq"] <= msn) & ~ob1_live & ~ob2_live
    )

    # --- insert: tie-break = first slot with cum >= pos.
    can = (cum >= op["a"]) & active
    jfirst = jnp.min(jnp.where(can, slot, S), axis=1, keepdims=True)
    j = jnp.where(jfirst < S, jfirst, n)

    # Obliterate-on-arrival neighbor rule.
    present = active & ~expired
    left_idx = jnp.max(jnp.where(present & (slot < j), slot, -1),
                       axis=1, keepdims=True)
    right_idx = jnp.min(jnp.where(present & (slot >= j), slot, S),
                        axis=1, keepdims=True)
    has_left = left_idx >= 0
    has_right = right_idx < S
    l1s = _at(cols["ob1_seq"], slot, left_idx, has_left, NOT_REMOVED)
    l2s = _at(cols["ob2_seq"], slot, left_idx, has_left, NOT_REMOVED)
    l1c = _at(cols["ob1_client"], slot, left_idx, has_left, NOT_REMOVED)
    l2c = _at(cols["ob2_client"], slot, left_idx, has_left, NOT_REMOVED)
    r1s = _at(cols["ob1_seq"], slot, right_idx, has_right, NOT_REMOVED)
    r2s = _at(cols["ob2_seq"], slot, right_idx, has_right, NOT_REMOVED)

    def killer_of(ls, lc):
        shared = (ls != NOT_REMOVED) & ((ls == r1s) | (ls == r2s))
        ok = shared & (ls > ref_seq) & (lc != client)
        return jnp.where(ok, ls, jnp.int32(NOT_REMOVED)), lc

    k1s, k1c = killer_of(l1s, l1c)
    k2s, k2c = killer_of(l2s, l2c)
    kill_seq = jnp.minimum(k1s, k2s)
    kill_client = jnp.where(k1s <= k2s, k1c, k2c)
    killed = kill_seq != NOT_REMOVED

    def shifted(f, newval):
        return jnp.where(slot == j, newval, _shift_up_from(f, slot, j))

    ins_cols = {
        "tstart": shifted(cols["tstart"], op["tstart"]),
        "tlen": shifted(cols["tlen"], op["tlen"]),
        "ins_seq": shifted(cols["ins_seq"], op["seq"]),
        "ins_client": shifted(cols["ins_client"], client),
        "rem_seq": shifted(cols["rem_seq"],
                           jnp.where(killed, kill_seq, NOT_REMOVED)),
        "rem_client": shifted(cols["rem_client"],
                              jnp.where(killed, kill_client, -1)),
        "rem2_seq": shifted(cols["rem2_seq"], NOT_REMOVED),
        "rem2_client": shifted(cols["rem2_client"], -1),
        "ob1_seq": shifted(cols["ob1_seq"],
                           jnp.where(killed, kill_seq, NOT_REMOVED)),
        "ob1_client": shifted(cols["ob1_client"],
                              jnp.where(killed, kill_client, -1)),
        "ob2_seq": shifted(cols["ob2_seq"], NOT_REMOVED),
        "ob2_client": shifted(cols["ob2_client"], -1),
    }
    ins_props = tuple(
        shifted(p, jnp.where(pv == PROP_NOT_TOUCHED, PROP_ABSENT, pv))
        for p, pv in zip(props, pvals)
    )
    cols = {f: jnp.where(is_ins, ins_cols[f], cols[f]) for f in _COL_FIELDS}
    props = tuple(
        jnp.where(is_ins, ip, p) for ip, p in zip(ins_props, props)
    )
    n = jnp.where(is_ins, n + 1, n)

    # --- remove / annotate / obliterate over [a, b) in the view.
    covered = (cum >= op["a"]) & (cum + v <= op["b"]) & (v > 0) & active

    is_rem_like = is_rem | is_obl
    first_win = covered & (cols["rem_seq"] == NOT_REMOVED) & is_rem_like
    again = covered & (cols["rem_seq"] != NOT_REMOVED) & is_rem_like
    second = again & (cols["rem2_seq"] == NOT_REMOVED)
    third = again & (cols["rem2_seq"] != NOT_REMOVED)
    obl_zero = active & ~expired & (v == 0) \
        & (cum > op["a"]) & (cum < op["b"]) & is_obl
    obl_zero_alive = obl_zero & (cols["rem_seq"] == NOT_REMOVED)
    first_win = first_win | obl_zero_alive
    stamp = (covered & is_obl) | obl_zero
    to_ob1 = stamp & (cols["ob1_seq"] == NOT_REMOVED)
    to_ob2 = stamp & ~to_ob1 & (cols["ob2_seq"] == NOT_REMOVED) \
        & (cols["ob1_seq"] != op["seq"])
    ob_over = stamp & (cols["ob1_seq"] != NOT_REMOVED) \
        & (cols["ob2_seq"] != NOT_REMOVED) \
        & (cols["ob1_seq"] != op["seq"]) & (cols["ob2_seq"] != op["seq"])
    cols = dict(
        cols,
        rem_seq=jnp.where(first_win, op["seq"], cols["rem_seq"]),
        rem_client=jnp.where(first_win, client, cols["rem_client"]),
        rem2_seq=jnp.where(second, op["seq"], cols["rem2_seq"]),
        rem2_client=jnp.where(second, client, cols["rem2_client"]),
        ob1_seq=jnp.where(to_ob1, op["seq"], cols["ob1_seq"]),
        ob1_client=jnp.where(to_ob1, client, cols["ob1_client"]),
        ob2_seq=jnp.where(to_ob2, op["seq"], cols["ob2_seq"]),
        ob2_client=jnp.where(to_ob2, client, cols["ob2_client"]),
    )
    overflow = overflow | jnp.any(third, axis=1, keepdims=True) \
        | jnp.any(ob_over, axis=1, keepdims=True)

    props = tuple(
        jnp.where((pv != PROP_NOT_TOUCHED) & (covered & is_ann), pv, p)
        for p, pv in zip(props, pvals)
    )
    return cols, props, n, overflow


def _fold_kernel(S: int, K: int, T: int, B: int, *refs):
    """A sublane batch of B documents per grid step: state lives in VMEM
    values across the whole tail; every block is 2-D ``(B, lanes)`` with
    128-multiple lanes, so the Mosaic block rule holds by construction.
    ``S`` is the PADDED slot lane count; ``T`` is the REAL op count — the
    loop never reads the pad rows of the (B, Tp) op blocks."""
    n_op = len(_OP_FIELDS)
    n_col = len(_COL_FIELDS)
    op_refs = refs[:n_op]
    pvals_refs = refs[n_op:n_op + K]
    in_cols = refs[n_op + K:n_op + K + n_col]
    in_props = refs[n_op + K + n_col:n_op + 2 * K + n_col]
    in_n, in_over = refs[n_op + 2 * K + n_col:n_op + 2 * K + n_col + 2]
    outs = refs[n_op + 2 * K + n_col + 2:]

    cols = {f: r[...] for f, r in zip(_COL_FIELDS, in_cols)}
    props = tuple(r[...] for r in in_props)
    n = in_n[:, :1]                 # value rides lane 0 of the 128-lane pad
    overflow = in_over[:, :1] != 0

    def body(t, carry):
        cols, props, n, overflow = carry
        op = {f: r[:, t].reshape(B, 1) for f, r in zip(_OP_FIELDS, op_refs)}
        pvals = tuple(r[:, t].reshape(B, 1) for r in pvals_refs)
        return _apply_op_rows(cols, props, n, overflow, op, pvals, S, K)

    cols, props, n, overflow = jax.lax.fori_loop(
        0, T, body, (cols, props, n, overflow))

    for f, r in zip(_COL_FIELDS, outs):
        r[...] = cols[f]
    for k in range(K):
        outs[len(_COL_FIELDS) + k][...] = props[k]
    lanes = outs[len(_COL_FIELDS) + K].shape[1]
    # Scalars broadcast across their 128-lane pad; the host reads lane 0.
    outs[len(_COL_FIELDS) + K][...] = jnp.broadcast_to(n, (B, lanes))
    outs[len(_COL_FIELDS) + K + 1][...] = jnp.broadcast_to(
        overflow.astype(jnp.int32), (B, lanes))


@functools.partial(jax.jit, static_argnames=("interpret",))
def replay_vmapped_pallas(state: MTState, ops: MTOps,
                          interpret: bool = True) -> MTState:
    """Drop-in replacement for ``replay_vmapped``: same (state, ops)
    pytrees in, same final MTState out — the fold itself runs as one
    Pallas program instance per 8-document sublane batch with
    VMEM-resident state.  ``D`` pads to a multiple of 8 with inert no-op
    documents (noop op rows never match a kind; zero state rows never
    activate); the slot and op lane dims pad to multiples of 128 (pad
    slots stay inactive — ``slot >= n`` — and pad op rows are never read:
    the loop bound is the real T).  All padding is sliced off on
    return."""
    D, S = state.tstart.shape
    K = state.props.shape[-1]
    T = ops.kind.shape[1]
    B = DOC_BLOCK
    Dp, Sp, Tp = _padded_dims(D, S, T)

    def pad2(x, rows, lanes, fill):
        pr, pl_ = rows - x.shape[0], lanes - x.shape[1]
        if pr == 0 and pl_ == 0:
            return x
        return jnp.pad(x, ((0, pr), (0, pl_)), constant_values=fill)

    inputs = (
        [pad2(getattr(ops, f).astype(jnp.int32), Dp, Tp, 0)
         for f in _OP_FIELDS]
        + [pad2(ops.pvals[:, :, k].astype(jnp.int32), Dp, Tp,
                _PROP_NOT_TOUCHED_FILL) for k in range(K)]
        + [pad2(getattr(state, f).astype(jnp.int32), Dp, Sp,
                _state_pad_fill(f)) for f in _COL_FIELDS]
        + [pad2(state.props[:, :, k].astype(jnp.int32), Dp, Sp,
                _PROP_ABSENT_FILL) for k in range(K)]
        + [pad2(state.n.astype(jnp.int32).reshape(D, 1), Dp, LANE, 0),
           pad2(state.overflow.astype(jnp.int32).reshape(D, 1), Dp, LANE,
                0)]
    )

    row = pl.BlockSpec((B, Sp), lambda d: (d, 0))
    op_row = pl.BlockSpec((B, Tp), lambda d: (d, 0))
    scalar = pl.BlockSpec((B, LANE), lambda d: (d, 0))

    in_specs = (
        [op_row] * (len(_OP_FIELDS) + K)
        + [row] * (len(_COL_FIELDS) + K) + [scalar, scalar]
    )
    out_specs = [row] * (len(_COL_FIELDS) + K) + [scalar, scalar]
    out_shape = (
        [jax.ShapeDtypeStruct((Dp, Sp), jnp.int32)]
        * (len(_COL_FIELDS) + K)
        + [jax.ShapeDtypeStruct((Dp, LANE), jnp.int32)] * 2
    )

    outs = pl.pallas_call(
        functools.partial(_fold_kernel, Sp, K, T, B),
        grid=(Dp // B,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*inputs)

    n_col = len(_COL_FIELDS)
    cols = {f: o[:D, :S] for f, o in zip(_COL_FIELDS, outs[:n_col])}
    return MTState(
        **cols,
        props=jnp.stack([outs[n_col + k][:D, :S] for k in range(K)],
                        axis=-1),
        n=outs[n_col + K][:D, 0],
        overflow=outs[n_col + K + 1][:D, 0].astype(bool),
    )


def pallas_fold_mode() -> str:
    """''/off (default), 'interpret', or 'tpu' (compiled Mosaic, which
    refuses the kernel today)."""
    import os

    mode = os.environ.get("FF_PALLAS_FOLD", "").lower()
    if mode in ("1", "tpu", "on"):
        return "tpu"
    if mode == "interpret":
        return "interpret"
    return ""
