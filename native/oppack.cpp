// liboppack — native op-log packing for the TPU replay path.
//
// The host-side hot loop of bulk catch-up is turning op streams into the
// padded (D, T) int32 arrays the merge-tree kernel folds (see
// fluidframework_tpu/ops/mergetree_kernel.py::pack_mergetree_batch).  The
// ingestion side encodes string-channel ops once into a flat binary record
// stream (ops/native_pack.py::encode_string_ops); this library consumes
// that stream and fills the arrays in one pass — no Python objects, no
// per-op dict lookups.
//
// Record layout (little-endian, packed; records in ascending seq):
//   u8  kind        (1=insert, 2=remove, 3=annotate, 4=obliterate)
//   i32 seq
//   i32 ref_seq
//   i32 min_seq     (stamped MSN — zamboni-expiry parity on device)
//   i32 client_idx  (interned by the encoder)
//   i32 a           (pos | start)
//   i32 b           (end; 0 for insert)
//   i32 n_props     (annotate property pairs)
//   i32 text_len    (insert only, BYTES of utf-8; 0 otherwise)
//   { i32 key_idx, i32 val_idx } * n_props   (val -1 == PROP_ABSENT)
//   u8  text[text_len]
//
// Text offsets in the arrays are in CHARACTERS (the Python arena is a str);
// the packer counts code points while copying utf-8 bytes, so the caller
// can decode the byte arena once and every (tstart, tlen) span aligns.
//
// API (C ABI, ctypes-consumed):
//   oppack_count(...)    — sizing pre-pass and the first/last seq, per stream
//   oppack_pack(...)     — fill a run of documents' rows of the batch arrays
//   oppack_extract(...)  — final device state → canonical summary-body JSON
//
// oppack_extract consumes the fused export buffer ([D, F, S] int32, see
// mergetree_kernel.EXPORT_SLOT_FIELDS) and emits, per document, the exact
// bytes of canonical_json(normalized_records): sorted keys, minimal
// separators, ensure_ascii=False (UTF-8 passthrough; only '"', '\\' and
// control chars escape, matching python json.dumps).  Slot rows carry two
// obliterate stamp pairs (rows 8..11); in-window stamps (> msn) emit as
// "ob":[[seq,"client"],...] and pin their tombstones past normal expiry.
// Overlap removers (row 7, plus one row pair per further slot after the
// property rows) emit as "ro":[...] in the oracle's sorted name order.

#include <cstdint>
#include <cstring>

namespace {
constexpr int64_t kHeader = 1 + 4 * 8;  // kind byte + 8 i32 fields
// Overlap slots past the first that an export may carry (the kernel's
// OV_SLOT_CAP less one).
constexpr int32_t kMaxExtraRemovers = 7;

inline int64_t count_codepoints(const uint8_t* p, int64_t n) {
    int64_t chars = 0;
    for (int64_t i = 0; i < n; ++i) {
        chars += (p[i] & 0xC0) != 0x80;
    }
    return chars;
}
}  // namespace

extern "C" {

// Sizing pre-pass over a run of `n` streams concatenated in `buf` (stream
// i at doc_off[i] .. doc_off[i+1]): out[5*i ..] receives stream i's op
// count, text bytes, text chars, and first and last seq (0, 0 when
// empty), the window the cache tiers match on.  Returns 0 on success; -1
// on truncated/malformed input.
int oppack_count(const uint8_t* buf, const int64_t* doc_off, int32_t n,
                 int64_t* out) {
    for (int32_t i = 0; i < n; ++i) {
        int64_t off = doc_off[i];
        const int64_t len = doc_off[i + 1];
        int64_t ops = 0, bytes = 0, chars = 0;
        int32_t first = 0, last = 0;
        while (off < len) {
            if (off + kHeader > len) return -1;
            int32_t fields[8];
            std::memcpy(fields, buf + off + 1, 4 * 8);
            const int32_t n_props = fields[6];
            const int32_t text_len = fields[7];
            off += kHeader;
            if (n_props < 0 || text_len < 0) return -1;
            if (off + 8 * static_cast<int64_t>(n_props) + text_len > len)
                return -1;
            off += 8 * static_cast<int64_t>(n_props);
            chars += count_codepoints(buf + off, text_len);
            bytes += text_len;
            off += text_len;
            if (ops == 0) first = fields[0];
            last = fields[0];
            ops += 1;
        }
        int64_t* o = out + 5 * static_cast<int64_t>(i);
        o[0] = ops;
        o[1] = bytes;
        o[2] = chars;
        o[3] = first;
        o[4] = last;
    }
    return 0;
}

}  // extern "C"

namespace {
// Packs one document's record stream into row-slices of the batch arrays.
// `pvals` is the (T, K) row in C order, pre-filled with PROP_NOT_TOUCHED.
// `key_map` / `val_map` translate the encoder's doc-local property key and
// value ids into the batch-global intern spaces (null = identity; negative
// value ids — PROP_ABSENT — pass through untranslated).
// Returns ops packed, or -1 on malformed input / capacity overflow.
int32_t pack_doc(const uint8_t* buf, int64_t len,
                 int32_t T, int32_t K, int64_t arena_base_chars,
                 int32_t* kind, int32_t* seq, int32_t* client,
                 int32_t* ref_seq, int32_t* min_seq, int32_t* a,
                 int32_t* b, int32_t* tstart, int32_t* tlen,
                 int32_t* pvals,
                 uint8_t* arena_out, int64_t arena_capacity,
                 int64_t* arena_bytes, int64_t* arena_chars,
                 const int32_t* key_map, int64_t n_keys,
                 const int32_t* val_map, int64_t n_vals) {
    int64_t off = 0;
    int32_t t = 0;
    int64_t out_bytes = 0, out_chars = 0;
    while (off < len) {
        if (off + kHeader > len) return -1;
        if (t >= T) return -1;
        const uint8_t k = buf[off];
        int32_t fields[8];
        std::memcpy(fields, buf + off + 1, 4 * 8);
        off += kHeader;
        const int32_t n_props = fields[6];
        const int32_t text_len = fields[7];
        if (n_props < 0 || text_len < 0) return -1;
        if (off + 8 * static_cast<int64_t>(n_props) + text_len > len)
            return -1;
        kind[t] = static_cast<int32_t>(k);
        seq[t] = fields[0];
        ref_seq[t] = fields[1];
        min_seq[t] = fields[2];
        client[t] = fields[3];
        a[t] = fields[4];
        b[t] = fields[5];
        for (int32_t i = 0; i < n_props; ++i) {
            int32_t pair[2];
            std::memcpy(pair, buf + off, 8);
            off += 8;
            int32_t col = pair[0];
            int32_t val = pair[1];
            if (key_map != nullptr) {
                if (col < 0 || col >= n_keys) return -1;
                col = key_map[col];
            }
            if (val_map != nullptr && val >= 0) {
                if (val >= n_vals) return -1;
                val = val_map[val];
            }
            if (col < 0 || col >= K) return -1;
            pvals[static_cast<int64_t>(t) * K + col] = val;
        }
        if (text_len > 0) {
            if (out_bytes + text_len > arena_capacity) return -1;
            std::memcpy(arena_out + out_bytes, buf + off, text_len);
            const int64_t chars = count_codepoints(buf + off, text_len);
            tstart[t] = static_cast<int32_t>(arena_base_chars + out_chars);
            tlen[t] = static_cast<int32_t>(chars);
            out_bytes += text_len;
            out_chars += chars;
            off += text_len;
        } else {
            tstart[t] = 0;
            tlen[t] = 0;
        }
        t += 1;
    }
    *arena_bytes = out_bytes;
    *arena_chars = out_chars;
    return t;
}
}  // namespace

extern "C" {

// Packs a run of `n` documents' record streams, concatenated in `buf`
// (document i's at doc_off[i] .. doc_off[i+1]), into rows row0 .. row0+n-1
// of the [D, T] op arrays (`pvals` [D, T, K]) in one call, so a chunk's
// pack crosses from Python once.  The run's text lands in `arena_out` in
// document order, its first char at arena char `arena_base_chars`;
// doc_chars[i] receives the run's chars before document i (doc_chars[n]
// the run's total).  key_map / val_map hold the documents' translation
// tables back to back, document i's at key_off[i] .. key_off[i+1] (an
// empty table = identity).  Returns the run's text bytes, or -1 on
// malformed input / capacity overflow.
int64_t oppack_pack(const uint8_t* buf, const int64_t* doc_off, int32_t n,
                    int32_t row0, int32_t T, int32_t K,
                    int64_t arena_base_chars,
                    int32_t* kind, int32_t* seq, int32_t* client,
                    int32_t* ref_seq, int32_t* min_seq, int32_t* a,
                    int32_t* b, int32_t* tstart, int32_t* tlen,
                    int32_t* pvals,
                    uint8_t* arena_out, int64_t arena_capacity,
                    int64_t* doc_chars,
                    const int32_t* key_map, const int64_t* key_off,
                    const int32_t* val_map, const int64_t* val_off) {
    int64_t bytes = 0, chars = 0;
    for (int32_t i = 0; i < n; ++i) {
        const int64_t row = static_cast<int64_t>(row0 + i) * T;
        const int64_t n_keys = key_off[i + 1] - key_off[i];
        const int64_t n_vals = val_off[i + 1] - val_off[i];
        int64_t doc_bytes = 0, doc_chars_i = 0;
        doc_chars[i] = chars;
        if (pack_doc(buf + doc_off[i], doc_off[i + 1] - doc_off[i], T, K,
                     arena_base_chars + chars,
                     kind + row, seq + row, client + row, ref_seq + row,
                     min_seq + row, a + row, b + row, tstart + row,
                     tlen + row, pvals + row * K,
                     arena_out + bytes, arena_capacity - bytes,
                     &doc_bytes, &doc_chars_i,
                     n_keys ? key_map + key_off[i] : nullptr, n_keys,
                     n_vals ? val_map + val_off[i] : nullptr,
                     n_vals) < 0)
            return -1;
        bytes += doc_bytes;
        chars += doc_chars_i;
    }
    doc_chars[n] = chars;
    return bytes;
}

// Final device state → canonical summary-body JSON for every document of a
// chunk, in one pass.  Layout contract with mergetree_kernel._export_state:
//   export_buf: [D, F, S] int32, C order, F = 12 + K + 2X + 1
//     rows 0..7: tstart, tlen, ins_seq, ins_client,
//                rem_seq, rem_client, rem2_seq, rem2_client
//     rows 8..11: ob1_seq, ob1_client, ob2_seq, ob2_client
//     rows 12..12+K-1: property value ids (PROP_ABSENT = -1)
//     rows 12+K..12+K+2X-1: (seq, client) of overlap slots 2..X+1
//     row  12+K+2X (misc): [n, overflow, live_len, 0...]
//   arena_utf8: the chunk text arena; tstart/tlen are CHAR offsets, so a
//     char→byte index is built once here.
//   client_json / key_json / val_json: pre-serialized JSON tokens
//     (canonical_json of each client name / property key / value),
//     flattened with offset tables.  clients are per-doc
//     (client_doc_start[d] .. client_doc_start[d+1] index the offs table);
//     client_rank (parallel to the tokens; may be null when X == 0) is
//     each client's rank in its document's sorted name order, the order
//     the oracle lists overlap removers in;
//     keys arrive in SORTED key order with key_cols[k] = the export row of
//     the k-th sorted key.
//   msn / final over per doc: msn drives tombstone expiry + seq clamping.
// Output: out (capacity out_cap) receives the concatenated bodies;
//   out_offs[d]..out_offs[d+1] delimit doc d.  Docs flagged by `skip` get
//   empty bodies (oracle-fallback docs).  Returns 0, or the required
//   capacity as a negative number minus one (caller regrows), or -1 on
//   malformed input (since -1 also means "need 0 bytes", capacity requests
//   use -(need)-2).
int64_t oppack_extract(
    const int32_t* export_buf, int32_t D, int32_t F, int32_t S, int32_t K,
    int32_t X,
    const uint8_t* arena_utf8, int64_t arena_bytes_len, int64_t arena_chars,
    const uint8_t* client_json, const int64_t* client_offs,
    const int32_t* client_doc_start, const int32_t* client_rank,
    const uint8_t* key_json, const int64_t* key_offs,
    const int32_t* key_cols,
    const uint8_t* val_json, const int64_t* val_offs, int32_t n_vals,
    const int32_t* msn, const uint8_t* skip,
    int32_t not_removed,
    uint8_t* out, int64_t out_cap, int64_t* out_offs) {
    if (X < 0 || X > kMaxExtraRemovers || F != 12 + K + 2 * X + 1 ||
        (X > 0 && client_rank == nullptr)) {
        return -1;
    }
    // char → byte index over the arena (one pass).
    int64_t* idx = new int64_t[arena_chars + 1];
    {
        int64_t c = 0;
        for (int64_t i = 0; i < arena_bytes_len; ++i) {
            if ((arena_utf8[i] & 0xC0) != 0x80) {
                if (c > arena_chars) { delete[] idx; return -1; }
                idx[c++] = i;
            }
        }
        if (c != arena_chars) { delete[] idx; return -1; }
        idx[arena_chars] = arena_bytes_len;
    }

    int64_t w = 0;  // write cursor; keeps counting past capacity
    bool fits = true;
    bool bad = false;
    auto put = [&](const uint8_t* p, int64_t n) {
        if (fits && w + n <= out_cap) std::memcpy(out + w, p, n);
        else fits = false;
        w += n;
    };
    auto put_lit = [&](const char* s) {
        put(reinterpret_cast<const uint8_t*>(s), std::strlen(s));
    };
    auto put_int = [&](int64_t v) {
        char tmp[24];
        int n = 0;
        if (v < 0) { tmp[n++] = '-'; v = -v; }
        char digits[20];
        int nd = 0;
        do { digits[nd++] = static_cast<char>('0' + v % 10); v /= 10; }
        while (v > 0);
        while (nd > 0) tmp[n++] = digits[--nd];
        put(reinterpret_cast<const uint8_t*>(tmp), n);
    };
    // Escaped UTF-8 emit (ensure_ascii=False): passthrough except
    // '"', '\\' and control chars — exactly python json.dumps.
    auto put_escaped = [&](const uint8_t* tp, int64_t tn) {
        int64_t run = 0;
        for (int64_t i = 0; i < tn; ++i) {
            const uint8_t ch = tp[i];
            if (!(ch == '"' || ch == '\\' || ch < 0x20)) { ++run; continue; }
            if (run) put(tp + i - run, run);
            run = 0;
            switch (ch) {
                case '"': put_lit("\\\""); break;
                case '\\': put_lit("\\\\"); break;
                case '\b': put_lit("\\b"); break;
                case '\t': put_lit("\\t"); break;
                case '\n': put_lit("\\n"); break;
                case '\f': put_lit("\\f"); break;
                case '\r': put_lit("\\r"); break;
                default: {
                    char u[6];
                    static const char* hex = "0123456789abcdef";
                    u[0] = '\\'; u[1] = 'u'; u[2] = '0'; u[3] = '0';
                    u[4] = hex[(ch >> 4) & 0xF];
                    u[5] = hex[ch & 0xF];
                    put(reinterpret_cast<const uint8_t*>(u), 6);
                }
            }
        }
        if (run) put(tp + tn - run, run);
    };
    auto put_client = [&](int32_t d, int32_t c) {
        const int32_t ci = client_doc_start[d] + c;
        if (ci >= client_doc_start[d + 1]) { bad = true; return; }
        put(client_json + client_offs[ci],
            client_offs[ci + 1] - client_offs[ci]);
    };

    const int64_t fs = static_cast<int64_t>(F) * S;
    for (int32_t d = 0; d < D && !bad; ++d) {
        out_offs[d] = w;
        if (skip != nullptr && skip[d]) continue;
        const int32_t* ex = export_buf + static_cast<int64_t>(d) * fs;
        const int32_t* p_tstart = ex + 0 * S;
        const int32_t* p_tlen = ex + 1 * S;
        const int32_t* p_ins_seq = ex + 2 * S;
        const int32_t* p_ins_client = ex + 3 * S;
        const int32_t* p_rem_seq = ex + 4 * S;
        const int32_t* p_rem_client = ex + 5 * S;
        const int32_t* p_rem2_client = ex + 7 * S;
        const int32_t* p_ob1_seq = ex + 8 * S;
        const int32_t* p_ob1_client = ex + 9 * S;
        const int32_t* p_ob2_seq = ex + 10 * S;
        const int32_t* p_ob2_client = ex + 11 * S;
        const int32_t n = ex[static_cast<int64_t>(12 + K + 2 * X) * S + 0];
        const int32_t doc_msn = msn[d];
        if (n < 0 || n > S) { bad = true; break; }

        // In-window obliterate stamps pin a tombstone past normal expiry
        // (tail inserts resolve their arrival verdict against it).
        auto live_stamps = [&](int32_t s) {
            int32_t count = 0;
            if (p_ob1_seq[s] != not_removed && p_ob1_seq[s] > doc_msn) ++count;
            if (p_ob2_seq[s] != not_removed && p_ob2_seq[s] > doc_msn) ++count;
            return count;
        };
        // The overlap removers of slot s (client ids), in sorted name
        // order; returns how many.
        const int32_t ndoc_clients =
            client_doc_start[d + 1] - client_doc_start[d];
        auto removers = [&](int32_t s, int32_t* out) {
            int32_t m = 0;
            if (p_rem2_client[s] >= 0) out[m++] = p_rem2_client[s];
            for (int32_t j = 0; j < X; ++j) {
                const int32_t c =
                    ex[(12 + K + 2 * static_cast<int64_t>(j) + 1) * S + s];
                if (c >= 0) out[m++] = c;
            }
            for (int32_t i = 0; i < m; ++i) {
                if (out[i] >= ndoc_clients) { bad = true; return 0; }
            }
            for (int32_t i = 1; i < m; ++i) {  // insertion sort by rank
                const int32_t c = out[i];
                const int32_t r = client_rank[client_doc_start[d] + c];
                int32_t k = i - 1;
                while (k >= 0 &&
                       client_rank[client_doc_start[d] + out[k]] > r) {
                    out[k + 1] = out[k];
                    --k;
                }
                out[k + 1] = c;
            }
            return m;
        };
        auto expired = [&](int32_t s) {
            const int32_t rs = p_rem_seq[s];
            return rs != not_removed && rs <= doc_msn &&
                   p_ins_seq[s] <= doc_msn && live_stamps(s) == 0;
        };
        // Merge-equality of two SURVIVING slots, mirroring
        // _extract_records: normalized (s, c), removal triple, overlap
        // remover, property row.  Expired tombstones between surviving
        // slots are invisible to the merge (python compares against the
        // last *emitted* record).
        auto meta_eq = [&](int32_t x, int32_t y) {
            const bool rx = p_rem_seq[x] != not_removed;
            const bool ry = p_rem_seq[y] != not_removed;
            const bool cx = p_ins_seq[x] <= doc_msn;
            const bool cy = p_ins_seq[y] <= doc_msn;
            if ((cx ? 0 : p_ins_seq[x]) != (cy ? 0 : p_ins_seq[y])) {
                return false;
            }
            if ((cx ? -1 : p_ins_client[x]) != (cy ? -1 : p_ins_client[y])) {
                return false;
            }
            if (rx != ry) return false;
            if (rx && (p_rem_seq[x] != p_rem_seq[y] ||
                       p_rem_client[x] != p_rem_client[y])) {
                return false;
            }
            if (X == 0) {
                if (p_rem2_client[x] != p_rem2_client[y]) return false;
            } else {
                int32_t rx_ids[kMaxExtraRemovers + 1];
                int32_t ry_ids[kMaxExtraRemovers + 1];
                const int32_t mx = removers(x, rx_ids);
                const int32_t my = removers(y, ry_ids);
                if (mx != my) return false;
                for (int32_t i = 0; i < mx; ++i) {
                    if (rx_ids[i] != ry_ids[i]) return false;
                }
            }
            // in-window stamp lists must match
            const bool o1x = p_ob1_seq[x] != not_removed &&
                             p_ob1_seq[x] > doc_msn;
            const bool o1y = p_ob1_seq[y] != not_removed &&
                             p_ob1_seq[y] > doc_msn;
            const bool o2x = p_ob2_seq[x] != not_removed &&
                             p_ob2_seq[x] > doc_msn;
            const bool o2y = p_ob2_seq[y] != not_removed &&
                             p_ob2_seq[y] > doc_msn;
            if (o1x != o1y || o2x != o2y) return false;
            if (o1x && (p_ob1_seq[x] != p_ob1_seq[y] ||
                        p_ob1_client[x] != p_ob1_client[y])) return false;
            if (o2x && (p_ob2_seq[x] != p_ob2_seq[y] ||
                        p_ob2_client[x] != p_ob2_client[y])) return false;
            for (int32_t k = 0; k < K; ++k) {
                if (ex[(12 + static_cast<int64_t>(k)) * S + x] !=
                    ex[(12 + static_cast<int64_t>(k)) * S + y]) {
                    return false;
                }
            }
            return true;
        };

        put_lit("[");
        bool first_rec = true;
        int32_t s = 0;
        while (s < n && !bad) {
            if (expired(s)) { ++s; continue; }
            // Gather the merge group: surviving slots equal to s, skipping
            // expired tombstones in between.
            // Two passes, no buffer: find the group end (cur), then emit
            // text by re-walking [s, cur) and skipping expired slots.
            int32_t cur = s + 1;
            while (cur < n) {
                if (expired(cur)) { ++cur; continue; }
                if (!meta_eq(s, cur)) break;
                ++cur;
            }

            const bool removed = p_rem_seq[s] != not_removed;
            const bool clamp = p_ins_seq[s] <= doc_msn;
            const int32_t seq_out = clamp ? 0 : p_ins_seq[s];
            const int32_t c_out = clamp ? -1 : p_ins_client[s];

            if (!first_rec) put_lit(",");
            first_rec = false;
            put_lit("{\"c\":");
            if (c_out < 0) put_lit("null");
            else put_client(d, c_out);
            if (live_stamps(s) > 0) {
                put_lit(",\"ob\":[");
                bool first_ob = true;
                const int32_t ob_seqs[2] = {p_ob1_seq[s], p_ob2_seq[s]};
                const int32_t ob_clients[2] = {p_ob1_client[s],
                                               p_ob2_client[s]};
                for (int i = 0; i < 2; ++i) {
                    if (ob_seqs[i] == not_removed || ob_seqs[i] <= doc_msn)
                        continue;
                    if (!first_ob) put_lit(",");
                    first_ob = false;
                    put_lit("[");
                    put_int(ob_seqs[i]);
                    put_lit(",");
                    put_client(d, ob_clients[i]);
                    put_lit("]");
                }
                put_lit("]");
            }
            bool has_props = false;
            for (int32_t k = 0; k < K && !has_props; ++k) {
                has_props = ex[(12 + static_cast<int64_t>(k)) * S + s] >= 0;
            }
            if (has_props) {
                put_lit(",\"p\":{");
                bool first_p = true;
                for (int32_t k = 0; k < K; ++k) {  // sorted key order
                    const int32_t col = key_cols[k];
                    const int32_t vid =
                        ex[(12 + static_cast<int64_t>(col)) * S + s];
                    if (vid < 0) continue;
                    if (vid >= n_vals) { bad = true; break; }
                    if (!first_p) put_lit(",");
                    first_p = false;
                    put(key_json + key_offs[k],
                        key_offs[k + 1] - key_offs[k]);
                    put_lit(":");
                    put(val_json + val_offs[vid],
                        val_offs[vid + 1] - val_offs[vid]);
                }
                put_lit("}");
            }
            if (removed) {
                put_lit(",\"rc\":");
                if (p_rem_client[s] < 0) put_lit("null");
                else put_client(d, p_rem_client[s]);
            }
            {
                int32_t ro_ids[kMaxExtraRemovers + 1];
                const int32_t m = removers(s, ro_ids);
                if (m > 0) {
                    put_lit(",\"ro\":[");
                    for (int32_t i = 0; i < m; ++i) {
                        if (i) put_lit(",");
                        put_client(d, ro_ids[i]);
                    }
                    put_lit("]");
                }
            }
            if (removed) {
                put_lit(",\"rs\":");
                put_int(p_rem_seq[s]);
            }
            put_lit(",\"s\":");
            put_int(seq_out);
            put_lit(",\"t\":\"");
            for (int32_t g = s; g < cur && !bad; ++g) {
                if (expired(g)) continue;
                const int64_t c0 = p_tstart[g];
                const int64_t cl = p_tlen[g];
                if (c0 < 0 || c0 + cl > arena_chars) { bad = true; break; }
                put_escaped(arena_utf8 + idx[c0], idx[c0 + cl] - idx[c0]);
            }
            put_lit("\"}");
            s = cur;
        }
        put_lit("]");
    }
    delete[] idx;
    if (bad) return -1;
    out_offs[D] = w;
    if (!fits) return -w - 2;
    return 0;
}

// oppack_widen — undo the export transfer encodings in one native pass:
// narrow (int16 / int8-pair) source buffer → the canonical [D, R_canon, S]
// int32 layout mergetree_kernel.widen_export produces (byte-identical;
// pinned by tests).  Replaces the numpy widen on the extraction hot path.
//
// desc: R_canon rows × 4 int32 = [mode, arg, fill, flags]
//   mode 0 = FILL      (constant `fill`)
//   mode 1 = ROW16     (arg = source row; int16 elements)
//   mode 2 = PAIR8     (arg = src_row * 2 + half; int16 lane holds two
//                       int8 values, half 0 = high byte, 1 = low byte)
//   mode 3 = MISC      (stitch misc[d, j] for j < misc_cols, else 0)
// flags bit0: remap sentinel_src → sentinel_dst
// flags bit1: re-add doc_base[d] on slots < n (live-slot tstart rebase);
//             n is read from the canonical misc row (always last, col 0).
int32_t oppack_widen(
    const int16_t* src, int32_t D, int32_t S,
    int32_t R_src, int32_t R_canon,
    const int16_t* misc, int32_t misc_cols,
    const int32_t* desc,
    const int32_t* doc_base,
    int32_t sentinel_src, int32_t sentinel_dst,
    int32_t* dst) {
    // Validate the desc table up front, like the per-doc `n` check below:
    // a source-row index past R_src (ROW16 directly, PAIR8 via arg/2) or a
    // MISC row without the misc output would read out of bounds.  -1, not
    // UB, on a malformed table.
    for (int32_t r = 0; r < R_canon; ++r) {
        const int32_t mode = desc[r * 4 + 0];
        const int32_t arg = desc[r * 4 + 1];
        if (mode == 1 && (arg < 0 || arg >= R_src)) return -1;
        if (mode == 2 && (arg < 0 || arg / 2 >= R_src)) return -1;
        if (mode == 3 && misc == nullptr) return -1;
        if (mode < 0 || mode > 3) return -1;
    }
    const int64_t src_doc = static_cast<int64_t>(R_src) * S;
    const int64_t dst_doc = static_cast<int64_t>(R_canon) * S;
    for (int32_t d = 0; d < D; ++d) {
        const int16_t* sp = src + static_cast<int64_t>(d) * src_doc;
        int32_t* dp = dst + static_cast<int64_t>(d) * dst_doc;
        // n for the live-slot rebase: misc col 0 (separate misc output in
        // the pair layout, last source row otherwise).
        const int32_t n = misc != nullptr
            ? misc[static_cast<int64_t>(d) * misc_cols + 0]
            : sp[static_cast<int64_t>(R_src - 1) * S + 0];
        if (n < 0 || n > S) return -1;
        for (int32_t r = 0; r < R_canon; ++r) {
            const int32_t mode = desc[r * 4 + 0];
            const int32_t arg = desc[r * 4 + 1];
            const int32_t fill = desc[r * 4 + 2];
            const int32_t flags = desc[r * 4 + 3];
            int32_t* row = dp + static_cast<int64_t>(r) * S;
            if (mode == 0) {
                for (int32_t s = 0; s < S; ++s) row[s] = fill;
                continue;
            }
            if (mode == 3) {
                for (int32_t s = 0; s < S; ++s)
                    row[s] = s < misc_cols
                        ? misc[static_cast<int64_t>(d) * misc_cols + s] : 0;
                continue;
            }
            if (mode == 1) {
                const int16_t* srow = sp + static_cast<int64_t>(arg) * S;
                for (int32_t s = 0; s < S; ++s) row[s] = srow[s];
            } else if (mode == 2) {
                const int16_t* srow =
                    sp + static_cast<int64_t>(arg / 2) * S;
                const bool hi = (arg % 2) == 0;
                for (int32_t s = 0; s < S; ++s) {
                    const uint16_t pair = static_cast<uint16_t>(srow[s]);
                    row[s] = static_cast<int8_t>(
                        hi ? (pair >> 8) : (pair & 0xFF));
                }
            } else {
                return -1;
            }
            if (flags & 1) {
                for (int32_t s = 0; s < S; ++s)
                    if (row[s] == sentinel_src) row[s] = sentinel_dst;
            }
            if ((flags & 2) && doc_base != nullptr) {
                const int32_t base = doc_base[d];
                for (int32_t s = 0; s < n; ++s) row[s] += base;
            }
        }
    }
    return 0;
}

}  // extern "C"
