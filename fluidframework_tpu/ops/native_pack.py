"""Native op packing: binary record streams + the liboppack C++ packer.

The catch-up service's prepare (``CatchupService._kernel_inputs``) encodes
each cold string channel's tail ONCE, straight from the decoded grouped
batches, into the flat binary record format documented in
``native/oppack.cpp`` (:func:`encode_channel_stream`); ``bench.py``'s
synthesis encodes message lists (:func:`encode_string_ops`).  Packing a
chunk for the device then runs in C++ — one pass per document filling the
padded (D, T) arrays and the shared text arena, no Python objects in the
loop, with the interpreter lock released (ctypes drops it), so the
pipeline's pack threads run in parallel.

It also hosts the extraction fast path: ``oppack_extract`` turns the fused
final-state export buffer into canonical summary-body JSON bytes for a whole
chunk in one C++ pass (see ``extract_bodies``).

Build: the library compiles on demand from ``native/oppack.cpp`` with g++.
The artifact is keyed by a content hash of the source
(``liboppack-<hash>.so``) so a stale binary can never shadow newer source —
mtimes are meaningless after a git checkout.  If no toolchain is available
the pure-Python encoder/packer pair keeps everything working — the native
path is a strictly optional accelerator with bit-identical output (asserted
by tests/test_native_pack.py).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import struct
import subprocess
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..protocol.messages import MessageType, SequencedMessage
from .interning import Interner

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "oppack.cpp")

_KINDS = {"insert": 1, "remove": 2, "annotate": 3, "obliterate": 4}
_HEADER = struct.Struct("<B8i")
_PAIR = struct.Struct("<2i")


# -- encoder (ingestion side; pure Python by design — runs once per op) -------


def encode_string_ops(
    ops: Sequence[SequencedMessage],
    client_intern,
    prop_key_intern=None,
    value_intern=None,
) -> bytes:
    """Sequence-channel ops → the flat binary record stream.

    ``client_intern`` / ``prop_key_intern`` / ``value_intern`` are
    ``Interner``-likes (callables via ``.intern``); symbol interning stays
    host-side so records carry dense ids only."""
    out = bytearray()
    for msg in ops:
        if msg.type is not MessageType.OP:
            continue
        op = msg.contents
        kind = _KINDS[op["kind"]]
        client = client_intern.intern(msg.client_id) \
            if msg.client_id is not None else -1
        if kind == 1:
            text = op["text"].encode("utf-8")
            a, b = op["pos"], 0
        else:
            text = b""
            a, b = op["start"], op["end"]
        props = op.get("props") or {}
        pairs = []
        for key, value in props.items():
            if prop_key_intern is None:
                raise ValueError("props present but no prop interner given")
            k = prop_key_intern.intern(key)
            v = -1 if value is None else value_intern.intern(value)
            pairs.append((k, v))
        out += _HEADER.pack(kind, msg.seq, msg.ref_seq, msg.min_seq,
                            client, a, b, len(pairs), len(text))
        for pair in pairs:
            out += _PAIR.pack(*pair)
        out += text
    return bytes(out)


def encode_channel_stream(decoded, ds_id: str, channel_id: str):
    """One channel's ops → ``(stream, clients, prop_keys, values)``, the
    binary record stream and its doc-local intern tables, in one pass over
    ``decoded`` (the ``(msg, batch)`` pairs of ``decode_stream``): the
    stream :func:`encode_string_ops` writes for the flattened ops, without
    building a message per sub-op.  Sub-ops keep their batch's seq.

    None where the channel must keep the message path: an interval op
    (intervals never ride the stream), an op kind the stream has no code
    for, an empty insert (the stream gives it no arena offset), or text
    that is not valid UTF-8 (a lone surrogate)."""
    out = bytearray()
    header, pair, kinds = _HEADER.pack, _PAIR.pack, _KINDS
    clients: Dict[str, int] = {}
    keys = values = None
    for msg, batch in decoded:
        for sub in batch["ops"]:
            if sub.get("ds") != ds_id or sub.get("channel") != channel_id:
                continue
            op = sub["contents"]
            kind = kinds.get(op["kind"])
            if kind is None:
                return None
            cid = msg.client_id
            if cid is None:
                client = -1
            else:
                client = clients.get(cid)
                if client is None:
                    client = clients[cid] = len(clients)
            if kind == 1:
                text = op["text"]
                if not text:
                    return None
                try:
                    text = text.encode("utf-8")
                except UnicodeEncodeError:
                    return None
                a, b = op["pos"], 0
            else:
                text = b""
                a, b = op["start"], op["end"]
            props = op.get("props")
            if props:
                if keys is None:
                    keys, values = Interner(), Interner()
                out += header(kind, msg.seq, msg.ref_seq, msg.min_seq,
                              client, a, b, len(props), len(text))
                for key, value in props.items():
                    out += pair(keys.intern(key),
                                -1 if value is None else values.intern(value))
            else:
                out += header(kind, msg.seq, msg.ref_seq, msg.min_seq,
                              client, a, b, 0, len(text))
            out += text
    return (bytes(out), list(clients),
            None if keys is None else list(keys.values),
            None if values is None or not len(values)
            else list(values.values))


def decode_string_ops(
    blob: bytes, clients: Sequence[str],
    prop_keys: Optional[Sequence[str]] = None,
    values: Optional[Sequence] = None,
) -> List[SequencedMessage]:
    """Inverse of :func:`encode_string_ops` — the oracle-fallback escape
    hatch for binary-only documents (rare; correctness over speed)."""
    out: List[SequencedMessage] = []
    off = 0
    kinds = {v: k for k, v in _KINDS.items()}
    while off < len(blob):
        kind, seq, ref, min_seq, client, a, b, n_props, text_len = \
            _HEADER.unpack_from(blob, off)
        off += _HEADER.size
        props = {}
        for _ in range(n_props):
            k, v = _PAIR.unpack_from(blob, off)
            off += 8
            props[prop_keys[k]] = None if v == -1 else values[v]
        text = blob[off:off + text_len].decode("utf-8")
        off += text_len
        name = kinds[kind]
        if name == "insert":
            contents = {"kind": "insert", "pos": a, "text": text}
            if props:
                contents["props"] = props
        else:
            contents = {"kind": name, "start": a, "end": b}
            if props:
                contents["props"] = props
        out.append(SequencedMessage(
            seq=seq, client_id=clients[client] if client >= 0 else None,
            client_seq=seq, ref_seq=ref, min_seq=min_seq,
            type=MessageType.OP, contents=contents,
        ))
    return out


# -- the native library --------------------------------------------------------


_lib_handle: Optional[ctypes.CDLL] = None
_lib_tried = False


def _build_library() -> Optional[str]:
    if not os.path.exists(_SRC):
        return None
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    lib_path = os.path.join(
        _REPO_ROOT, "native", f"liboppack-{digest}.so"
    )
    if os.path.exists(lib_path):
        return lib_path
    tmp = lib_path + f".tmp{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
             "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120,
        )
        # g++ wrote the artifact through its own descriptors: reopen and
        # fsync before publishing, or a crash can install a torn .so.
        fd = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
        os.replace(tmp, lib_path)  # commit-point: native library publish
    except (OSError, subprocess.SubprocessError):
        return None
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass
    # Superseded hash builds: safe to drop (an mmap'd inode survives the
    # unlink for any process still using it).
    import glob

    for old in glob.glob(os.path.join(_REPO_ROOT, "native",
                                      "liboppack-*.so")):
        if old != lib_path:
            try:
                os.unlink(old)
            except OSError:
                pass
    return lib_path


def load_library() -> Optional[ctypes.CDLL]:
    """The compiled packer, or None (pure-Python fallback)."""
    global _lib_handle, _lib_tried
    if _lib_tried:
        return _lib_handle
    _lib_tried = True
    path = _build_library()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    i32 = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    i64 = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    lib.oppack_count.restype = ctypes.c_int
    lib.oppack_count.argtypes = [ctypes.c_char_p, i64, ctypes.c_int32, i64]
    lib.oppack_pack.restype = ctypes.c_int64
    lib.oppack_pack.argtypes = [
        ctypes.c_char_p, i64, ctypes.c_int32,              # run
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,    # row0, T, K
        ctypes.c_int64,                                    # arena base
    ] + [i32] * 10 + [
        np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
        i64,                                               # doc_chars
        i32, i64, i32, i64,                                # key/val maps
    ]
    lib.oppack_extract.restype = ctypes.c_int64
    lib.oppack_extract.argtypes = [
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # export
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32,                                    # extra slots
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,   # arena
        ctypes.c_char_p,                                   # client_json
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ctypes.c_void_p,                                   # client_rank
        ctypes.c_char_p,                                   # key_json
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ctypes.c_char_p,                                   # val_json
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_int32,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # msn
        np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),  # skip
        ctypes.c_int32,
        np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),  # out
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),  # out_offs
    ]
    lib.oppack_widen.restype = ctypes.c_int32
    lib.oppack_widen.argtypes = [
        np.ctypeslib.ndpointer(np.int16, flags="C_CONTIGUOUS"),  # src
        ctypes.c_int32, ctypes.c_int32,                          # D, S
        ctypes.c_int32, ctypes.c_int32,                          # R_src/canon
        ctypes.c_void_p, ctypes.c_int32,                         # misc, cols
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # desc
        ctypes.c_void_p,                                         # doc_base
        ctypes.c_int32, ctypes.c_int32,                          # sentinels
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # dst
    ]
    _lib_handle = lib
    return lib


def _run_offsets(blobs: Sequence[bytes]) -> np.ndarray:
    offs = np.zeros(len(blobs) + 1, np.int64)
    np.cumsum([len(b) for b in blobs], out=offs[1:])
    return offs


def count_streams(blobs: Sequence[bytes]) -> np.ndarray:
    """(n_ops, text_bytes, text_chars, first_seq, last_seq) of each stream,
    as the rows of an int64 array (both seqs 0 for an empty stream): one
    native call for the whole run."""
    lib = load_library()
    if lib is None:
        return np.asarray([_count_py(b) for b in blobs],
                          np.int64).reshape(len(blobs), 5)
    out = np.zeros((len(blobs), 5), np.int64)
    if lib.oppack_count(b"".join(blobs), _run_offsets(blobs), len(blobs),
                        out) != 0:
        raise ValueError("malformed binary op stream")
    return out


def _count_py(blob: bytes) -> Tuple[int, int, int, int, int]:
    off, n, tb, tc = 0, 0, 0, 0
    first = last = 0
    while off < len(blob):
        (_kind, seq, _ref, _msn, _cl, _a, _b, n_props,
         text_len) = _HEADER.unpack_from(blob, off)
        off += _HEADER.size + 8 * n_props
        text = blob[off:off + text_len]
        if len(text) != text_len:
            raise ValueError("malformed binary op stream")
        off += text_len
        tb += text_len
        tc += len(text.decode("utf-8"))
        if n == 0:
            first = seq
        last = seq
        n += 1
    return n, tb, tc, first, last


def stream_offset(blob: bytes, n: int) -> int:
    """Byte offset of record ``n`` of a stream (``len(blob)`` when it holds
    exactly ``n``): a header walk that decodes no text.  The cache tiers
    read a record's seq, or the records past a cached window, from it."""
    off = 0
    for _ in range(n):
        (_kind, _seq, _ref, _msn, _cl, _a, _b, n_props,
         text_len) = _HEADER.unpack_from(blob, off)
        off += _HEADER.size + 8 * n_props + text_len
    return off


def stream_seq_at(blob: bytes, i: int) -> int:
    """The seq of record ``i`` of a stream."""
    return _HEADER.unpack_from(blob, stream_offset(blob, i))[1]


def binary_has_obliterate(blob: bytes) -> bool:
    """Header-only scan: does the stream contain an obliterate record?"""
    off = 0
    while off < len(blob):
        kind, _s, _r, _m, _c, _a, _b, n_props, text_len = \
            _HEADER.unpack_from(blob, off)
        if kind == _KINDS["obliterate"]:
            return True
        off += _HEADER.size + 8 * n_props + text_len
    return False


#: the nine [D, T] op fields in oppack_pack's argument order
_ROW_FIELDS = ("kind", "seq", "client", "ref_seq", "min_seq", "a", "b",
               "tstart", "tlen")


def _flat_maps(maps: Sequence[Sequence[int]]):
    offs = _run_offsets(maps)
    flat = np.fromiter((i for m in maps for i in m), np.int32,
                       count=int(offs[-1]))
    return flat, offs


def pack_streams(op: Dict[str, np.ndarray], row0: int,
                 blobs: Sequence[bytes], key_maps: Sequence[Sequence[int]],
                 val_maps: Sequence[Sequence[int]], arena_base_chars: int,
                 text_bytes: int) -> Tuple[str, np.ndarray]:
    """Fill rows ``row0 ..`` of the batch op arrays from a run of documents'
    streams, in one native call that holds no interpreter lock, so the
    pipeline's pack threads run in parallel.  ``key_maps``/``val_maps``
    translate each document's encoder-local property key / value ids into
    the batch-global intern spaces (empty = identity); ``text_bytes`` is
    the run's text size (``count_streams``).  Returns the run's text, to
    append to the arena at char ``arena_base_chars``, and the run's chars
    before each document (n + 1 entries).

    Without the native library the rows fill in Python (``_pack_py``)."""
    n = len(blobs)
    if not 0 <= row0 <= row0 + n <= op["kind"].shape[0]:
        raise ValueError(f"rows {row0}..{row0 + n} outside the batch")
    lib = load_library()
    if lib is None:
        arena = bytearray()
        doc_chars = np.zeros(n + 1, np.int64)
        K = int(op["pvals"].shape[2])
        for i, blob in enumerate(blobs):
            d = row0 + i
            row = {key: op[key][d] for key in _ROW_FIELDS + ("pvals",)}
            before = len(arena)
            _pack_py(blob, row, K, arena_base_chars + int(doc_chars[i]),
                     arena, key_maps[i] or None, val_maps[i] or None)
            doc_chars[i + 1] = doc_chars[i] + len(
                arena[before:].decode("utf-8"))
        return arena.decode("utf-8"), doc_chars
    T, K = int(op["kind"].shape[1]), int(op["pvals"].shape[2])
    scratch = np.empty(max(text_bytes, 1), np.uint8)
    doc_chars = np.zeros(n + 1, np.int64)
    key_flat, key_offs = _flat_maps(key_maps)
    val_flat, val_offs = _flat_maps(val_maps)
    nbytes = lib.oppack_pack(
        b"".join(blobs), _run_offsets(blobs), n, row0, T, K,
        arena_base_chars, *(op[f] for f in _ROW_FIELDS), op["pvals"],
        scratch, scratch.nbytes, doc_chars,
        key_flat, key_offs, val_flat, val_offs,
    )
    if nbytes < 0:
        raise ValueError("malformed binary op stream")
    return scratch[:nbytes].tobytes().decode("utf-8"), doc_chars


def _pack_py(blob: bytes, row: Dict[str, np.ndarray], K: int,
             arena_base_chars: int, arena: bytearray,
             key_map: Optional[np.ndarray] = None,
             val_map: Optional[np.ndarray] = None) -> int:
    off, t, chars = 0, 0, 0
    while off < len(blob):
        kind, seq, ref, min_seq, client, a, b, n_props, text_len = \
            _HEADER.unpack_from(blob, off)
        off += _HEADER.size
        row["kind"][t] = kind
        row["seq"][t] = seq
        row["ref_seq"][t] = ref
        row["min_seq"][t] = min_seq
        row["client"][t] = client
        row["a"][t] = a
        row["b"][t] = b
        for _ in range(n_props):
            k, v = _PAIR.unpack_from(blob, off)
            off += 8
            if key_map is not None:
                k = int(key_map[k])
            if val_map is not None and v >= 0:
                v = int(val_map[v])
            row["pvals"][t, k] = v
        if text_len:
            text = blob[off:off + text_len]
            off += text_len
            n_chars = len(text.decode("utf-8"))
            row["tstart"][t] = arena_base_chars + chars
            row["tlen"][t] = n_chars
            arena += text
            chars += n_chars
        else:
            row["tstart"][t] = 0
            row["tlen"][t] = 0
        t += 1
    return t


# -- native summary-body extraction -------------------------------------------


def extract_bodies(
    export_np: np.ndarray,
    arena_text: str,
    doc_clients: Sequence[Sequence[str]],
    prop_keys: Sequence[str],
    values: Sequence,
    msn: np.ndarray,
    skip: np.ndarray,
    not_removed: int,
    ov_extra: int = 0,
) -> Optional[List[bytes]]:
    """Canonical summary-body JSON bytes for every doc of a chunk, via the
    C++ extractor; None when the native library is unavailable (callers
    fall back to the per-slot Python extraction).

    ``export_np``: the fused [D, F, S] int32 export buffer, with
    ``ov_extra`` overlap slots past the first (a row pair each after the
    property rows);
    ``doc_clients``: per-doc client-id tables in intern order;
    ``prop_keys`` / ``values``: the chunk-global intern tables;
    ``msn`` int32[D]; ``skip`` uint8[D] flags oracle-fallback docs."""
    from ..protocol.summary import canonical_json

    lib = load_library()
    if lib is None:
        return None
    D, F, S = export_np.shape
    K = F - 13 - 2 * ov_extra
    export_np = np.ascontiguousarray(export_np, np.int32)

    def flatten(tokens: Sequence[bytes]):
        offs = np.zeros(len(tokens) + 1, np.int64)
        for i, tok in enumerate(tokens):
            offs[i + 1] = offs[i] + len(tok)
        return b"".join(tokens), offs

    def json_str(s) -> bytes:
        # Fast path for the overwhelmingly common simple client id: no
        # char needing JSON escaping (quote, backslash, controls) and
        # pure ASCII — byte-equal to canonical_json then.  Anything else
        # takes the canonical serializer.
        if isinstance(s, str) and s.isascii() and '"' not in s \
                and "\\" not in s and (not s or min(s) >= " "):
            return b'"%s"' % s.encode()
        return canonical_json(s)

    client_tokens: List[bytes] = []
    doc_start = np.zeros(D + 1, np.int32)
    ranks: List[int] = []
    for d, clients in enumerate(doc_clients):
        client_tokens.extend(json_str(c) for c in clients)
        doc_start[d + 1] = len(client_tokens)
        if ov_extra:
            # Each client's place in the doc's sorted names: the order
            # the oracle lists overlap removers in.
            by_name = sorted(range(len(clients)), key=clients.__getitem__)
            rank = [0] * len(clients)
            for r, i in enumerate(by_name):
                rank[i] = r
            ranks.extend(rank)
    client_blob, client_offs = flatten(client_tokens)
    client_rank = np.asarray(ranks, np.int32)

    order = sorted(range(len(prop_keys)), key=lambda i: prop_keys[i])
    key_cols = np.asarray(order, np.int32) if order else \
        np.zeros(0, np.int32)
    key_blob, key_offs = flatten(
        [canonical_json(prop_keys[i]) for i in order]
    )
    # The export carries K (bucketed) property rows but only
    # len(prop_keys) real columns; pad key_cols so k indexes stay aligned.
    if K > len(order):
        # Point the padding at the unused bucket columns themselves —
        # they are always PROP_ABSENT in the export, so they emit nothing.
        pad = np.zeros(K, np.int32)
        pad[:len(order)] = key_cols
        pad[len(order):] = np.arange(len(order), K, dtype=np.int32)
        key_cols = pad
        key_offs = np.concatenate(
            [key_offs,
             np.full(K - len(order), key_offs[-1], np.int64)]
        )
    val_blob, val_offs = flatten([canonical_json(v) for v in values])

    arena_bytes = arena_text.encode("utf-8")
    msn = np.ascontiguousarray(msn, np.int32)
    skip = np.ascontiguousarray(skip, np.uint8)
    out_offs = np.zeros(D + 1, np.int64)
    cap = max(len(arena_bytes) * 2 + D * 64 + int(export_np.shape[2]) * D * 8,
              1 << 16)
    for _attempt in range(3):
        out = np.empty(cap, np.uint8)  # C++ writes [0, out_offs[D])
        rc = lib.oppack_extract(
            export_np, D, F, S, K, ov_extra,
            arena_bytes, len(arena_bytes), len(arena_text),
            client_blob, client_offs, doc_start,
            client_rank.ctypes.data if ov_extra else None,
            key_blob, key_offs, key_cols,
            val_blob, val_offs, len(values),
            msn, skip, not_removed,
            out, cap, out_offs,
        )
        if rc == 0:
            buf = out[:out_offs[D]].tobytes()  # copy used extent only
            return [
                buf[out_offs[d]:out_offs[d + 1]] for d in range(D)
            ]
        if rc == -1:
            raise ValueError("oppack_extract: malformed export buffer")
        cap = int(-rc - 2) + 1024
    raise RuntimeError("oppack_extract: capacity negotiation failed")
