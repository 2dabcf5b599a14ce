// Native columnar storage engine for opentsdb_tpu.
//
// Plays the role the HBase storage layer + asynchbase client played for the
// reference (SURVEY.md §2.6 storage schema; compaction's space rationale,
// /root/reference/src/core/CompactionQueue.java:40-56: amortize per-cell
// overhead by packing cells — here, whole chunks compress together).
//
// Design:
//   * per-series storage = sealed compressed chunks + an uncompressed
//     append tail (the CompactionQueue analog: the tail seals into a
//     compressed chunk once it reaches CHUNK_POINTS).
//   * chunk codec: delta-of-delta zig-zag varint timestamps (time-series
//     deltas are near-constant) + XOR'd IEEE754 value bits varint-packed
//     (Gorilla-style), plus an is-int bitmap so Java-long exactness
//     survives: integer points carry their int64 bits instead of a double.
//   * reads decompress + merge + sort + last-write-wins dedup, mirroring
//     MemStore.Series.normalize semantics.
//   * save/load: length-prefixed dump of keys + chunks (snapshot file).
//
// C ABI only (driven from Python via ctypes).

#include <algorithm>
#include <charconv>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <map>
#include <mutex>
#include <unordered_map>
#include <string>
#include <vector>

#define EXPORT extern "C" __attribute__((visibility("default")))

namespace {

constexpr size_t CHUNK_POINTS = 512;

// ---------------------------------------------------------------- varint

inline void put_varint(std::vector<uint8_t>& out, uint64_t v) {
    while (v >= 0x80) {
        out.push_back(static_cast<uint8_t>(v) | 0x80);
        v >>= 7;
    }
    out.push_back(static_cast<uint8_t>(v));
}

inline uint64_t get_varint(const uint8_t* data, size_t& pos) {
    uint64_t v = 0;
    int shift = 0;
    for (;;) {
        uint8_t b = data[pos++];
        v |= static_cast<uint64_t>(b & 0x7F) << shift;
        if (!(b & 0x80)) return v;
        shift += 7;
    }
}

inline uint64_t zigzag(int64_t v) {
    return (static_cast<uint64_t>(v) << 1) ^ static_cast<uint64_t>(v >> 63);
}

inline int64_t unzigzag(uint64_t v) {
    return static_cast<int64_t>(v >> 1) ^ -static_cast<int64_t>(v & 1);
}

// ---------------------------------------------------------------- point

struct Point {
    int64_t ts;
    double fval;
    int64_t ival;
    uint8_t is_int;
};

// ---------------------------------------------------------------- chunk

struct Chunk {
    std::vector<uint8_t> data;  // compressed
    size_t n = 0;
    int64_t first_ts = 0;
    int64_t last_ts = 0;

    static Chunk compress(const Point* pts, size_t n) {
        Chunk c;
        c.n = n;
        if (n == 0) return c;
        c.first_ts = pts[0].ts;
        c.last_ts = pts[n - 1].ts;
        std::vector<uint8_t>& out = c.data;
        out.reserve(n * 4);
        // timestamps: first raw, then delta-of-delta zig-zag varints
        put_varint(out, zigzag(pts[0].ts));
        int64_t prev_ts = pts[0].ts;
        int64_t prev_delta = 0;
        for (size_t i = 1; i < n; i++) {
            int64_t delta = pts[i].ts - prev_ts;
            put_varint(out, zigzag(delta - prev_delta));
            prev_delta = delta;
            prev_ts = pts[i].ts;
        }
        // is-int bitmap
        for (size_t i = 0; i < n; i += 8) {
            uint8_t b = 0;
            for (size_t j = 0; j < 8 && i + j < n; j++)
                if (pts[i + j].is_int) b |= (1u << j);
            out.push_back(b);
        }
        // values: ints as zig-zag delta varints, floats as XOR'd bit
        // patterns (Gorilla-style, varint-packed)
        int64_t prev_int = 0;
        uint64_t prev_bits = 0;
        for (size_t i = 0; i < n; i++) {
            if (pts[i].is_int) {
                put_varint(out, zigzag(pts[i].ival - prev_int));
                prev_int = pts[i].ival;
            } else {
                uint64_t bits;
                std::memcpy(&bits, &pts[i].fval, 8);
                put_varint(out, bits ^ prev_bits);
                prev_bits = bits;
            }
        }
        return c;
    }

    void decompress(std::vector<Point>& out) const {
        if (n == 0) return;
        size_t pos = 0;
        const uint8_t* d = data.data();
        size_t base = out.size();
        out.resize(base + n);
        // timestamps
        int64_t ts = unzigzag(get_varint(d, pos));
        out[base].ts = ts;
        int64_t prev_delta = 0;
        for (size_t i = 1; i < n; i++) {
            prev_delta += unzigzag(get_varint(d, pos));
            ts += prev_delta;
            out[base + i].ts = ts;
        }
        // is-int bitmap
        size_t bitmap_pos = pos;
        pos += (n + 7) / 8;
        for (size_t i = 0; i < n; i++) {
            out[base + i].is_int =
                (d[bitmap_pos + i / 8] >> (i % 8)) & 1;
        }
        // values
        int64_t prev_int = 0;
        uint64_t prev_bits = 0;
        for (size_t i = 0; i < n; i++) {
            if (out[base + i].is_int) {
                prev_int += unzigzag(get_varint(d, pos));
                out[base + i].ival = prev_int;
                out[base + i].fval = static_cast<double>(prev_int);
            } else {
                prev_bits ^= get_varint(d, pos);
                double f;
                std::memcpy(&f, &prev_bits, 8);
                out[base + i].fval = f;
                out[base + i].ival = 0;
            }
        }
    }
};

// ---------------------------------------------------------------- series

struct Series {
    std::string key;            // opaque identity bytes from Python
    std::vector<Chunk> chunks;
    std::vector<Point> tail;    // uncompressed append buffer
    bool sorted = true;
    int64_t max_ts = INT64_MIN;
    std::mutex mu;

    size_t size() const {
        size_t total = tail.size();
        for (const auto& c : chunks) total += c.n;
        return total;
    }

    size_t bytes() const {
        size_t total = tail.capacity() * sizeof(Point);
        for (const auto& c : chunks) total += c.data.capacity();
        return total;
    }

    void append(int64_t ts, double fval, int64_t ival, uint8_t is_int) {
        std::lock_guard<std::mutex> lock(mu);
        if (ts <= max_ts) sorted = false;
        max_ts = std::max(max_ts, ts);
        tail.push_back(Point{ts, fval, ival, is_int});
        if (sorted && tail.size() >= CHUNK_POINTS) seal_locked();
    }

    void seal_locked() {
        if (tail.empty()) return;
        chunks.push_back(Chunk::compress(tail.data(), tail.size()));
        tail.clear();
        tail.shrink_to_fit();
    }

    // full materialization: decompress + sort + dedup (last wins).
    // dedup=false keeps duplicate timestamps (stable order, so the last
    // write for a timestamp stays last) — used by snapshot restore so a
    // dirty series round-trips as dirty instead of being silently healed.
    void materialize(std::vector<Point>& out, bool dedup = true) {
        out.clear();
        for (const auto& c : chunks) c.decompress(out);
        out.insert(out.end(), tail.begin(), tail.end());
        if (!sorted || chunks.size() > 1) {
            std::stable_sort(out.begin(), out.end(),
                             [](const Point& a, const Point& b) {
                                 return a.ts < b.ts;
                             });
        }
        // last-write-wins dedup
        if (dedup && !out.empty()) {
            size_t w = 0;
            for (size_t r = 1; r < out.size(); r++) {
                if (out[r].ts == out[w].ts) {
                    out[w] = out[r];
                } else {
                    out[++w] = out[r];
                }
            }
            out.resize(w + 1);
        }
    }

    // normalize: materialize then re-seal as sorted chunks
    void normalize() {
        std::lock_guard<std::mutex> lock(mu);
        if (sorted && chunks.size() <= 1) return;
        std::vector<Point> pts;
        materialize(pts);
        chunks.clear();
        for (size_t i = 0; i < pts.size(); i += CHUNK_POINTS) {
            size_t n = std::min(CHUNK_POINTS, pts.size() - i);
            chunks.push_back(Chunk::compress(pts.data() + i, n));
        }
        tail.clear();
        sorted = true;
    }
};

// ---------------------------------------------------------------- engine

struct Engine {
    std::vector<Series*> series;
    std::map<std::string, int64_t> by_key;
    std::mutex mu;

    ~Engine() {
        for (auto* s : series) delete s;
    }
};

thread_local std::vector<Point> g_scratch;

}  // namespace

EXPORT void* eng_create() { return new Engine(); }

EXPORT void eng_destroy(void* h) { delete static_cast<Engine*>(h); }

EXPORT int64_t eng_series(void* h, const uint8_t* key, int32_t key_len) {
    Engine* eng = static_cast<Engine*>(h);
    std::string k(reinterpret_cast<const char*>(key), key_len);
    std::lock_guard<std::mutex> lock(eng->mu);
    auto it = eng->by_key.find(k);
    if (it != eng->by_key.end()) return it->second;
    int64_t sid = static_cast<int64_t>(eng->series.size());
    Series* s = new Series();
    s->key = std::move(k);
    eng->series.push_back(s);
    eng->by_key.emplace(eng->series.back()->key, sid);
    return sid;
}

EXPORT int32_t eng_num_series(void* h) {
    Engine* eng = static_cast<Engine*>(h);
    std::lock_guard<std::mutex> lock(eng->mu);
    return static_cast<int32_t>(eng->series.size());
}

EXPORT int32_t eng_series_key(void* h, int64_t sid, uint8_t* out,
                              int32_t max_len) {
    Engine* eng = static_cast<Engine*>(h);
    Series* s = eng->series[sid];
    int32_t n = std::min<int32_t>(max_len,
                                  static_cast<int32_t>(s->key.size()));
    std::memcpy(out, s->key.data(), n);
    return static_cast<int32_t>(s->key.size());
}

EXPORT void eng_append(void* h, int64_t sid, int64_t ts, double fval,
                       int64_t ival, int32_t is_int) {
    Engine* eng = static_cast<Engine*>(h);
    eng->series[sid]->append(ts, fval, ival,
                             static_cast<uint8_t>(is_int));
}

EXPORT void eng_append_batch(void* h, int64_t sid, const int64_t* ts,
                             const double* fval, const int64_t* ival,
                             const uint8_t* is_int, int64_t n) {
    Engine* eng = static_cast<Engine*>(h);
    Series* s = eng->series[sid];
    std::lock_guard<std::mutex> lock(s->mu);
    for (int64_t i = 0; i < n; i++) {
        int64_t t = ts[i];
        if (t <= s->max_ts) s->sorted = false;
        s->max_ts = std::max(s->max_ts, t);
        s->tail.push_back(Point{t, fval[i], ival[i], is_int[i]});
    }
    if (s->sorted && s->tail.size() >= CHUNK_POINTS) s->seal_locked();
}

EXPORT int64_t eng_series_len(void* h, int64_t sid) {
    Engine* eng = static_cast<Engine*>(h);
    Series* s = eng->series[sid];
    std::lock_guard<std::mutex> lock(s->mu);
    return static_cast<int64_t>(s->size());
}

EXPORT int64_t eng_series_bytes(void* h, int64_t sid) {
    Engine* eng = static_cast<Engine*>(h);
    Series* s = eng->series[sid];
    std::lock_guard<std::mutex> lock(s->mu);
    return static_cast<int64_t>(s->bytes());
}

// Materialize [start, end] into caller buffers sized via eng_series_len.
// Returns the number of points written.
EXPORT int64_t eng_window(void* h, int64_t sid, int64_t start, int64_t end,
                          int64_t* out_ts, double* out_val,
                          int64_t* out_ival, uint8_t* out_isint,
                          int64_t max_n) {
    Engine* eng = static_cast<Engine*>(h);
    Series* s = eng->series[sid];
    std::lock_guard<std::mutex> lock(s->mu);
    s->materialize(g_scratch);
    auto lo = std::lower_bound(
        g_scratch.begin(), g_scratch.end(), start,
        [](const Point& p, int64_t v) { return p.ts < v; });
    auto hi = std::upper_bound(
        g_scratch.begin(), g_scratch.end(), end,
        [](int64_t v, const Point& p) { return v < p.ts; });
    int64_t n = 0;
    for (auto it = lo; it != hi && n < max_n; ++it, ++n) {
        out_ts[n] = it->ts;
        out_val[n] = it->fval;
        out_ival[n] = it->ival;
        out_isint[n] = it->is_int;
    }
    return n;
}

// Like eng_window over the full range, but duplicates survive (snapshot
// restore fidelity: a series persisted dirty must restore dirty).
EXPORT int64_t eng_window_raw(void* h, int64_t sid, int64_t* out_ts,
                              double* out_val, int64_t* out_ival,
                              uint8_t* out_isint, int64_t max_n) {
    Engine* eng = static_cast<Engine*>(h);
    Series* s = eng->series[sid];
    std::lock_guard<std::mutex> lock(s->mu);
    s->materialize(g_scratch, /*dedup=*/false);
    int64_t n = 0;
    for (auto it = g_scratch.begin(); it != g_scratch.end() && n < max_n;
         ++it, ++n) {
        out_ts[n] = it->ts;
        out_val[n] = it->fval;
        out_ival[n] = it->ival;
        out_isint[n] = it->is_int;
    }
    return n;
}

EXPORT int64_t eng_delete_range(void* h, int64_t sid, int64_t start,
                                int64_t end) {
    Engine* eng = static_cast<Engine*>(h);
    Series* s = eng->series[sid];
    std::lock_guard<std::mutex> lock(s->mu);
    s->materialize(g_scratch);
    std::vector<Point> kept;
    kept.reserve(g_scratch.size());
    int64_t removed = 0;
    for (const auto& p : g_scratch) {
        if (p.ts >= start && p.ts <= end) {
            removed++;
        } else {
            kept.push_back(p);
        }
    }
    s->chunks.clear();
    for (size_t i = 0; i < kept.size(); i += CHUNK_POINTS) {
        size_t n = std::min(CHUNK_POINTS, kept.size() - i);
        s->chunks.push_back(Chunk::compress(kept.data() + i, n));
    }
    s->tail.clear();
    s->sorted = true;
    s->max_ts = kept.empty() ? INT64_MIN : kept.back().ts;
    return removed;
}

EXPORT void eng_normalize(void* h, int64_t sid) {
    Engine* eng = static_cast<Engine*>(h);
    eng->series[sid]->normalize();
}

EXPORT int64_t eng_total_bytes(void* h) {
    Engine* eng = static_cast<Engine*>(h);
    std::lock_guard<std::mutex> lock(eng->mu);
    int64_t total = 0;
    for (auto* s : eng->series) total += s->bytes();
    return total;
}

// ---------------------------------------------------------------- save/load

EXPORT int32_t eng_save(void* h, const char* path) {
    Engine* eng = static_cast<Engine*>(h);
    FILE* f = std::fopen(path, "wb");
    if (!f) return -1;
    std::lock_guard<std::mutex> lock(eng->mu);
    uint64_t magic = 0x545044424E474E45ull;  // "ENGNBDPT"-ish tag
    std::fwrite(&magic, 8, 1, f);
    uint64_t n_series = eng->series.size();
    std::fwrite(&n_series, 8, 1, f);
    for (auto* s : eng->series) {
        std::lock_guard<std::mutex> slock(s->mu);
        s->seal_locked();
        uint64_t klen = s->key.size();
        std::fwrite(&klen, 8, 1, f);
        std::fwrite(s->key.data(), 1, klen, f);
        uint64_t n_chunks = s->chunks.size();
        std::fwrite(&n_chunks, 8, 1, f);
        uint8_t flags = s->sorted ? 1 : 0;
        std::fwrite(&flags, 1, 1, f);
        std::fwrite(&s->max_ts, 8, 1, f);
        for (const auto& c : s->chunks) {
            uint64_t n = c.n;
            uint64_t len = c.data.size();
            std::fwrite(&n, 8, 1, f);
            std::fwrite(&c.first_ts, 8, 1, f);
            std::fwrite(&c.last_ts, 8, 1, f);
            std::fwrite(&len, 8, 1, f);
            std::fwrite(c.data.data(), 1, len, f);
        }
    }
    std::fclose(f);
    return 0;
}

EXPORT void* eng_load(const char* path) {
    FILE* f = std::fopen(path, "rb");
    if (!f) return nullptr;
    uint64_t magic = 0;
    if (std::fread(&magic, 8, 1, f) != 1 ||
        magic != 0x545044424E474E45ull) {
        std::fclose(f);
        return nullptr;
    }
    Engine* eng = new Engine();
    uint64_t n_series = 0;
    std::fread(&n_series, 8, 1, f);
    for (uint64_t i = 0; i < n_series; i++) {
        Series* s = new Series();
        uint64_t klen = 0;
        std::fread(&klen, 8, 1, f);
        s->key.resize(klen);
        std::fread(s->key.data(), 1, klen, f);
        uint64_t n_chunks = 0;
        std::fread(&n_chunks, 8, 1, f);
        uint8_t flags = 1;
        std::fread(&flags, 1, 1, f);
        s->sorted = flags & 1;
        std::fread(&s->max_ts, 8, 1, f);
        for (uint64_t j = 0; j < n_chunks; j++) {
            Chunk c;
            uint64_t n = 0, len = 0;
            std::fread(&n, 8, 1, f);
            std::fread(&c.first_ts, 8, 1, f);
            std::fread(&c.last_ts, 8, 1, f);
            std::fread(&len, 8, 1, f);
            c.n = n;
            c.data.resize(len);
            std::fread(c.data.data(), 1, len, f);
            s->chunks.push_back(std::move(c));
        }
        int64_t sid = static_cast<int64_t>(eng->series.size());
        eng->series.push_back(s);
        eng->by_key.emplace(s->key, sid);
    }
    std::fclose(f);
    return eng;
}

// ================================================================ bulk put
//
// Native fast path for POST /api/put bodies (the reference's ingest
// scale claim, README:12-15, flows through PutDataPointRpc:272 ->
// TSDB.addPoint per point).  The Python bulk path (TSDB.add_points_bulk)
// already amortizes locks and column appends; profiling shows the
// remaining ~75% is the per-point Python loop: JSON object walk,
// validation, value classification, tag canonicalization.  This parser
// does all of that in one pass over the raw body bytes and hands Python
// back columnar arrays plus a distinct-series key table, so Python cost
// becomes O(distinct series), not O(points).
//
// Semantics mirror tsdb.py EXACTLY (error strings included) — any
// construct whose Python behavior is exotic (non-string metric/tags,
// arbitrary-precision timestamps, bool timestamps) returns FALLBACK so
// the caller reruns the Python path; behavior can never silently drift
// for inputs the native path accepts.  Tag canonicalization: tags sort
// bytewise on UTF-8 keys == Python's sorted() on code points.

namespace putparse {

struct PutBatch {
    std::vector<int64_t> ts;        // normalized ms
    std::vector<double> fval;
    std::vector<int64_t> ival;
    std::vector<uint8_t> isint;
    std::vector<int32_t> group;     // -1 on error
    std::vector<int64_t> span;      // 2*i: start, 2*i+1: end byte offsets
    // errors are SPARSE (parallel arrays, point index ascending) — a
    // per-point string pair would dominate allocation on clean bodies
    std::vector<int64_t> err_idx;
    std::vector<std::string> err_msg;
    std::vector<std::string> err_kind;  // "ValueError" | "TypeError"
    // group table: canonical (sorted-tag) identity keys plus the FIRST-
    // OCCURRENCE original-order form.  Python resolves series keys from
    // the original order so UID ASSIGNMENT order matches the per-point
    // path exactly (tagk/tagv ids are user-visible via /api/uid).
    std::vector<std::string> gkeys;       // canonical, identity
    std::vector<std::string> gorig;       // original tag order, exposed
    std::unordered_map<std::string, int32_t> gindex;
    // reused scratch (steady-state zero allocation per point)
    std::string ckey_scratch;
    std::string orig_scratch;
};

struct Parser {
    const char* p;
    const char* end;
    bool fallback = false;

    explicit Parser(const char* data, size_t len)
        : p(data), end(data + len) {}

    void ws() {
        while (p < end && (*p == ' ' || *p == '\t' || *p == '\n' ||
                           *p == '\r'))
            p++;
    }
    bool lit(const char* s) {
        size_t n = std::strlen(s);
        if (static_cast<size_t>(end - p) < n || std::memcmp(p, s, n) != 0)
            return false;
        p += n;
        return true;
    }
    // JSON string -> UTF-8 std::string; false on malformed
    bool str(std::string& out) {
        out.clear();
        if (p >= end || *p != '"') return false;
        p++;
        while (p < end) {
            unsigned char c = static_cast<unsigned char>(*p);
            if (c == '"') { p++; return true; }
            if (c == '\\') {
                if (++p >= end) return false;
                char e = *p++;
                switch (e) {
                    case '"': out.push_back('"'); break;
                    case '\\': out.push_back('\\'); break;
                    case '/': out.push_back('/'); break;
                    case 'b': out.push_back('\b'); break;
                    case 'f': out.push_back('\f'); break;
                    case 'n': out.push_back('\n'); break;
                    case 'r': out.push_back('\r'); break;
                    case 't': out.push_back('\t'); break;
                    case 'u': {
                        if (end - p < 4) return false;
                        unsigned cp = 0;
                        for (int i = 0; i < 4; i++) {
                            char h = *p++;
                            cp <<= 4;
                            if (h >= '0' && h <= '9') cp |= h - '0';
                            else if (h >= 'a' && h <= 'f') cp |= h - 'a' + 10;
                            else if (h >= 'A' && h <= 'F') cp |= h - 'A' + 10;
                            else return false;
                        }
                        bool paired = false;
                        if (cp >= 0xD800 && cp <= 0xDBFF && end - p >= 6 &&
                            p[0] == '\\' && p[1] == 'u') {
                            unsigned lo = 0;
                            const char* q = p + 2;
                            bool ok = true;
                            for (int i = 0; i < 4; i++) {
                                char h = q[i];
                                lo <<= 4;
                                if (h >= '0' && h <= '9') lo |= h - '0';
                                else if (h >= 'a' && h <= 'f')
                                    lo |= h - 'a' + 10;
                                else if (h >= 'A' && h <= 'F')
                                    lo |= h - 'A' + 10;
                                else { ok = false; break; }
                            }
                            if (ok && lo >= 0xDC00 && lo <= 0xDFFF) {
                                cp = 0x10000 + ((cp - 0xD800) << 10)
                                     + (lo - 0xDC00);
                                p += 6;
                                paired = true;
                            }
                        }
                        // Lone surrogates are valid JSON (json.loads
                        // keeps them as Python surrogate code points)
                        // but have no UTF-8 encoding — the Python path
                        // owns that exotic case.
                        if (cp >= 0xD800 && cp <= 0xDFFF && !paired) {
                            fallback = true;
                            cp = 0xFFFD;
                        }
                        // encode UTF-8
                        if (cp < 0x80) out.push_back(static_cast<char>(cp));
                        else if (cp < 0x800) {
                            out.push_back(static_cast<char>(0xC0 | (cp >> 6)));
                            out.push_back(static_cast<char>(
                                0x80 | (cp & 0x3F)));
                        } else if (cp < 0x10000) {
                            out.push_back(static_cast<char>(0xE0 | (cp >> 12)));
                            out.push_back(static_cast<char>(
                                0x80 | ((cp >> 6) & 0x3F)));
                            out.push_back(static_cast<char>(
                                0x80 | (cp & 0x3F)));
                        } else {
                            out.push_back(static_cast<char>(0xF0 | (cp >> 18)));
                            out.push_back(static_cast<char>(
                                0x80 | ((cp >> 12) & 0x3F)));
                            out.push_back(static_cast<char>(
                                0x80 | ((cp >> 6) & 0x3F)));
                            out.push_back(static_cast<char>(
                                0x80 | (cp & 0x3F)));
                        }
                        break;
                    }
                    default: return false;
                }
            } else {
                out.push_back(static_cast<char>(c));
                p++;
            }
        }
        return false;  // unterminated
    }
    // skip any JSON value (for unknown keys); false on malformed
    bool skip() {
        ws();
        if (p >= end) return false;
        char c = *p;
        if (c == '"') { std::string s_; return str(s_); }
        if (c == '{' || c == '[') {
            char open = c, close = (c == '{') ? '}' : ']';
            int depth = 0;
            bool in_str = false;
            while (p < end) {
                char d = *p;
                if (in_str) {
                    if (d == '\\') { p++; if (p >= end) return false; }
                    else if (d == '"') in_str = false;
                } else {
                    if (d == '"') in_str = true;
                    else if (d == open) depth++;
                    else if (d == close) {
                        if (--depth == 0) { p++; return true; }
                    }
                }
                p++;
            }
            return false;
        }
        // number / literal
        const char* q = p;
        while (q < end && *q != ',' && *q != '}' && *q != ']' &&
               *q != ' ' && *q != '\t' && *q != '\n' && *q != '\r')
            q++;
        if (q == p) return false;
        p = q;
        return true;
    }
};

// Python-int grammar: optional sign, digits with single underscores
// BETWEEN digits (int("1_0") == 10).  Returns false if not an integer
// literal by Python rules.
inline bool py_int(const std::string& t, bool& overflow, int64_t& out) {
    size_t i = 0;
    bool neg = false;
    overflow = false;
    out = 0;
    if (i < t.size() && (t[i] == '+' || t[i] == '-')) {
        neg = t[i] == '-';
        i++;
    }
    if (i >= t.size()) return false;
    bool prev_digit = false;
    bool acc_overflow = false;
    uint64_t acc = 0;
    for (; i < t.size(); i++) {
        char c = t[i];
        if (c == '_') {
            // Python int(): single underscores BETWEEN digits only
            if (!prev_digit || i + 1 >= t.size()) return false;
            prev_digit = false;
            continue;
        }
        if (c < '0' || c > '9') return false;
        prev_digit = true;
        uint64_t d = static_cast<uint64_t>(c - '0');
        if (acc > (UINT64_MAX - d) / 10) acc_overflow = true;
        else acc = acc * 10 + d;
    }
    if (!prev_digit) return false;
    // Java-long range check (Python ints are unbounded; the CALLER
    // rejects out-of-range with "out of long range")
    uint64_t lim = neg ? (1ULL << 63) : (1ULL << 63) - 1;
    if (acc_overflow || acc > lim) {
        overflow = true;
        return true;
    }
    out = neg ? (acc == (1ULL << 63) ? INT64_MIN
                                     : -static_cast<int64_t>(acc))
              : static_cast<int64_t>(acc);
    return true;
}

// Python-float grammar is strtod plus underscores-between-digits and
// without hex floats.  Returns false if not parseable as Python float.
inline bool py_float(const std::string& t, double& out) {
    if (t.empty()) return false;
    std::string clean;
    clean.reserve(t.size());
    bool prev_digit = false;
    for (size_t i = 0; i < t.size(); i++) {
        char c = t[i];
        if (c == '_') {
            bool next_digit = i + 1 < t.size() && t[i + 1] >= '0' &&
                              t[i + 1] <= '9';
            if (!prev_digit || !next_digit) return false;
            continue;
        }
        if (c == 'x' || c == 'X') return false;  // no hex floats
        prev_digit = c >= '0' && c <= '9';
        clean.push_back(c);
    }
    const char* s = clean.c_str();
    char* endp = nullptr;
    out = std::strtod(s, &endp);
    return endp == s + clean.size() && endp != s;
}

// simplified Python repr() of a decoded string (enough for error
// messages on realistic inputs; exotic escapes fall back)
inline bool py_repr(const std::string& s, std::string& out) {
    bool has_sq = s.find('\'') != std::string::npos;
    bool has_dq = s.find('"') != std::string::npos;
    char quote = (has_sq && !has_dq) ? '"' : '\'';
    out.clear();
    out.push_back(quote);
    for (unsigned char c : s) {
        if (c < 0x20 || c == 0x7F) return false;   // control chars: punt
        if (c == static_cast<unsigned char>(quote)) {
            out.push_back('\\');
        } else if (c == '\\') {
            out.push_back('\\');
        }
        out.push_back(static_cast<char>(c));
    }
    out.push_back(quote);
    return true;
}

// repr of a double the way Python renders it in error messages
inline std::string py_float_str(double v) {
    char buf[64];
    double r = v;
    std::snprintf(buf, sizeof buf, "%.17g", r);
    // Python uses repr shortest round-trip; try %.15g, %.16g first
    for (int prec = 15; prec <= 17; prec++) {
        std::snprintf(buf, sizeof buf, "%.*g", prec, r);
        if (std::strtod(buf, nullptr) == r) break;
    }
    std::string s(buf);
    if (s.find('.') == std::string::npos &&
        s.find('e') == std::string::npos &&
        s.find('n') == std::string::npos &&
        s.find('i') == std::string::npos)
        s += ".0";
    return s;
}

constexpr int64_t SECOND_MASK_LO = 0x100000000LL;  // ts >= 2^32 -> already ms

struct PointScratch {
    std::string metric;
    size_t ntags = 0;         // live prefix of `tags` (slots are reused)
    bool metric_seen = false, metric_is_str = false;
    std::string ts_str;       // lexeme or decoded string
    bool ts_seen = false, ts_is_str = false, ts_is_num = false;
    double ts_num = 0;
    bool ts_num_is_int = false;
    int64_t ts_int = 0;
    std::string val_str;
    bool val_seen = false, val_is_str = false, val_is_num = false,
         val_is_bool = false, val_bool = false, val_is_null = false;
    double val_num = 0;
    bool val_num_is_int = false;
    int64_t val_int = 0;
    bool val_int_overflow = false;
    std::vector<std::pair<std::string, std::string>> tags;
    bool tags_seen = false, tags_empty = false;
};

}  // namespace putparse

using putparse::PutBatch;
using putparse::Parser;
using putparse::PointScratch;

namespace putparse {

// parse one number token with STRICT JSON grammar
// ('-'? (0|[1-9][0-9]*) ('.'[0-9]+)? ([eE][+-]?[0-9]+)?); sets is_int if
// the lexeme has no . e E.  Leniency here would make the API accept
// bodies (+5, 007, .5) that json.loads rejects, so accept/reject
// behavior would depend on whether the native library is present.
inline bool number(Parser& P, double& out, bool& is_int, int64_t& ival,
                   bool& overflow, std::string& lexeme) {
    const char* q = P.p;
    if (q < P.end && *q == '-') q++;
    if (q >= P.end || *q < '0' || *q > '9') return false;
    if (*q == '0') q++;                       // no leading zeros
    else while (q < P.end && *q >= '0' && *q <= '9') q++;
    bool frac = false;
    if (q < P.end && *q == '.') {
        frac = true;
        q++;
        if (q >= P.end || *q < '0' || *q > '9') return false;
        while (q < P.end && *q >= '0' && *q <= '9') q++;
    }
    if (q < P.end && (*q == 'e' || *q == 'E')) {
        frac = true;
        q++;
        if (q < P.end && (*q == '+' || *q == '-')) q++;
        if (q >= P.end || *q < '0' || *q > '9') return false;
        while (q < P.end && *q >= '0' && *q <= '9') q++;
    }
    lexeme.assign(P.p, q - P.p);
    is_int = !frac;
    if (is_int) {
        if (!py_int(lexeme, overflow, ival)) return false;
        out = static_cast<double>(ival);
        if (overflow) out = 0;
    } else {
        char* endp = nullptr;
        out = std::strtod(lexeme.c_str(), &endp);
        if (endp != lexeme.c_str() + lexeme.size()) return false;
    }
    P.p = q;
    return true;
}

}  // namespace putparse


namespace putparse {

enum FieldKind : uint8_t {
    K_ABSENT = 0, K_NULL, K_STRING, K_NUMBER, K_BOOL, K_OBJECT, K_ARRAY,
    K_EMPTY_OBJECT
};

struct RawPoint {
    PointScratch s;
    uint8_t metric_kind = K_ABSENT;
    uint8_t ts_kind = K_ABSENT;
    uint8_t val_kind = K_ABSENT;
    uint8_t tags_kind = K_ABSENT;
    int64_t span_start = 0, span_end = 0;
    std::string ts_lexeme;    // original number lexeme for %s rendering
    std::string val_lexeme;

    // Reset for reuse between points: strings keep their capacity, so a
    // long body parses with near-zero steady-state allocation (storing
    // one RawPoint per point cost ~10 allocs x N and dominated the
    // parse at 400k points).
    void reset() {
        metric_kind = ts_kind = val_kind = tags_kind = K_ABSENT;
        span_start = span_end = 0;
        ts_lexeme.clear();
        val_lexeme.clear();
        s.metric.clear();
        s.ts_str.clear();
        s.val_str.clear();
        s.ntags = 0;          // slots stay allocated for reuse
        s.metric_seen = s.metric_is_str = false;
        s.ts_seen = s.ts_is_str = s.ts_is_num = false;
        s.ts_num = 0;
        s.ts_num_is_int = false;
        s.ts_int = 0;
        s.val_seen = s.val_is_str = s.val_is_num = false;
        s.val_is_bool = s.val_bool = s.val_is_null = false;
        s.val_num = 0;
        s.val_num_is_int = false;
        s.val_int = 0;
        s.val_int_overflow = false;
        s.tags_seen = s.tags_empty = false;
    }
};

// Parse one datapoint object into RawPoint; returns false -> malformed
// JSON (whole-body fallback).  Sets P.fallback for exotic-but-valid
// constructs whose Python behavior we refuse to mirror natively.
inline bool parse_point(Parser& P, RawPoint& rp, const char* base) {
    P.ws();
    if (P.p >= P.end || *P.p != '{') return false;
    rp.span_start = P.p - base;
    P.p++;
    bool first = true;
    std::string key;              // reused across fields
    for (;;) {
        P.ws();
        if (P.p < P.end && *P.p == '}') {
            P.p++;
            break;
        }
        if (!first) {
            if (P.p >= P.end || *P.p != ',') return false;
            P.p++;
            P.ws();
        }
        first = false;
        if (!P.str(key)) return false;
        P.ws();
        if (P.p >= P.end || *P.p != ':') return false;
        P.p++;
        P.ws();
        if (key == "metric") {
            if (P.p < P.end && *P.p == '"') {
                if (!P.str(rp.s.metric)) return false;
                rp.metric_kind = K_STRING;
            } else if (P.lit("null")) {
                rp.metric_kind = K_NULL;
            } else {
                rp.metric_kind = K_NUMBER;  // any non-string: fallback later
                P.fallback = true;
                if (!P.skip()) return false;
            }
        } else if (key == "timestamp") {
            if (P.p < P.end && *P.p == '"') {
                if (!P.str(rp.s.ts_str)) return false;
                rp.ts_kind = K_STRING;
            } else if (P.lit("null")) {
                rp.ts_kind = K_NULL;
            } else if (P.lit("true") || P.lit("false")) {
                rp.ts_kind = K_BOOL;
                P.fallback = true;
            } else if (P.p < P.end && (*P.p == '{' || *P.p == '[')) {
                const char* before = P.p;
                char open = *P.p;
                if (!P.skip()) return false;
                // Python: {} == {} -> missing field; others TypeError
                std::string body(before, P.p - before);
                bool empty = true;
                for (char c : body)
                    if (c != '{' && c != '}' && c != '[' && c != ']' &&
                        c != ' ' && c != '\t' && c != '\n' && c != '\r')
                        empty = false;
                rp.ts_kind = (empty && open == '{') ? K_EMPTY_OBJECT
                                                    : K_OBJECT;
                if (rp.ts_kind == K_OBJECT) P.fallback = true;
            } else {
                bool is_int = false, of = false;
                int64_t iv = 0;
                if (!number(P, rp.s.ts_num, is_int, iv, of,
                            rp.ts_lexeme)) return false;
                if (of) { P.fallback = true; }   // arbitrary-precision ts
                rp.ts_kind = K_NUMBER;
                rp.s.ts_is_num = true;
                rp.s.ts_num_is_int = is_int;
                rp.s.ts_int = iv;
            }
        } else if (key == "value") {
            if (P.p < P.end && *P.p == '"') {
                if (!P.str(rp.s.val_str)) return false;
                rp.val_kind = K_STRING;
            } else if (P.lit("null")) {
                rp.val_kind = K_NULL;
            } else if (P.lit("true")) {
                rp.val_kind = K_BOOL;
                rp.s.val_bool = true;
            } else if (P.lit("false")) {
                rp.val_kind = K_BOOL;
                rp.s.val_bool = false;
            } else if (P.p < P.end && (*P.p == '{' || *P.p == '[')) {
                const char* before = P.p;
                char open = *P.p;
                if (!P.skip()) return false;
                std::string body(before, P.p - before);
                bool empty = true;
                for (char c : body)
                    if (c != '{' && c != '}' && c != '[' && c != ']' &&
                        c != ' ' && c != '\t' && c != '\n' && c != '\r')
                        empty = false;
                rp.val_kind = (empty && open == '{') ? K_EMPTY_OBJECT
                                                     : K_OBJECT;
                if (rp.val_kind == K_OBJECT) P.fallback = true;
            } else {
                bool is_int = false, of = false;
                int64_t iv = 0;
                if (!number(P, rp.s.val_num, is_int, iv, of,
                            rp.val_lexeme)) return false;
                rp.val_kind = K_NUMBER;
                rp.s.val_is_num = true;
                rp.s.val_num_is_int = is_int;
                rp.s.val_int = iv;
                rp.s.val_int_overflow = of;
            }
        } else if (key == "tags") {
            if (P.p < P.end && *P.p == '{') {
                P.p++;
                rp.s.ntags = 0;
                bool tfirst = true;
                for (;;) {
                    P.ws();
                    if (P.p < P.end && *P.p == '}') { P.p++; break; }
                    if (!tfirst) {
                        if (P.p >= P.end || *P.p != ',') return false;
                        P.p++;
                        P.ws();
                    }
                    tfirst = false;
                    // The last-wins dedupe below is O(ntags) per tag —
                    // fine to the 8-tag limit (+ slack), quadratic for
                    // adversarial bodies; beyond the cap the Python
                    // path's O(n) dict handles it (the point errors
                    // with "Too many tags" either way).
                    if (rp.s.ntags >= 64) {
                        P.fallback = true;
                        rp.s.ntags = 63;
                    }
                    // parse straight into a reused slot (string
                    // capacities persist across points)
                    if (rp.s.ntags == rp.s.tags.size())
                        rp.s.tags.emplace_back();
                    auto& slot = rp.s.tags[rp.s.ntags];
                    if (!P.str(slot.first)) return false;
                    P.ws();
                    if (P.p >= P.end || *P.p != ':') return false;
                    P.p++;
                    P.ws();
                    if (P.p < P.end && *P.p == '"') {
                        if (!P.str(slot.second)) return false;
                    } else {
                        P.fallback = true;     // non-string tag value
                        if (!P.skip()) return false;
                        slot.second.clear();
                    }
                    // canonical-key separators must stay unambiguous; NUL
                    // would truncate the c_char_p group-key return (ADVICE r3)
                    if (slot.first.find_first_of("\x1E\x1F", 0) != std::string::npos ||
                        slot.first.find('\0', 0) != std::string::npos ||
                        slot.second.find_first_of("\x1E\x1F", 0) != std::string::npos ||
                        slot.second.find('\0', 0) != std::string::npos)
                        P.fallback = true;
                    bool replaced = false;     // JSON duplicate key: last wins
                    for (size_t ti = 0; ti < rp.s.ntags; ti++)
                        if (rp.s.tags[ti].first == slot.first) {
                            rp.s.tags[ti].second = slot.second;
                            replaced = true;
                        }
                    if (!replaced) rp.s.ntags++;
                }
                rp.tags_kind = rp.s.ntags == 0 ? K_EMPTY_OBJECT : K_OBJECT;
            } else if (P.lit("null")) {
                rp.tags_kind = K_NULL;
            } else {
                rp.tags_kind = K_ARRAY;
                P.fallback = true;
                if (!P.skip()) return false;
            }
        } else {
            if (!P.skip()) return false;   // unknown fields are ignored
        }
    }
    rp.span_end = P.p - base;
    return true;
}


// canonical series-key + group-table insert shared by the JSON and
// telnet paths (step 5 of finish_point): identity = metric + bytewise-
// SORTED tags; the stored gorig form keeps ORIGINAL tag order so Python
// key resolution assigns UIDs in per-point-path order.
inline int32_t assign_group(const std::string& metric,
                            const PointScratch& s, PutBatch& out) {
    uint32_t tag_order[8];
    for (uint32_t i = 0; i < s.ntags; i++) tag_order[i] = i;
    std::sort(tag_order, tag_order + s.ntags,
              [&s](uint32_t a, uint32_t b) {
                  return s.tags[a] < s.tags[b];
              });
    std::string& ckey = out.ckey_scratch;
    ckey.clear();
    ckey.append(metric);
    for (uint32_t i = 0; i < s.ntags; i++) {
        const auto& kv = s.tags[tag_order[i]];
        ckey.push_back('\x1F');
        ckey.append(kv.first);
        ckey.push_back('\x1E');
        ckey.append(kv.second);
    }
    auto it = out.gindex.find(ckey);
    if (it != out.gindex.end()) return it->second;
    int32_t gid = static_cast<int32_t>(out.gkeys.size());
    out.gkeys.push_back(ckey);
    std::string& orig = out.orig_scratch;
    orig.clear();
    orig.append(metric);
    for (uint32_t i = 0; i < s.ntags; i++) {
        const auto& kv = s.tags[i];
        orig.push_back('\x1F');
        orig.append(kv.first);
        orig.push_back('\x1E');
        orig.append(kv.second);
    }
    out.gorig.push_back(orig);
    out.gindex.emplace(ckey, gid);
    return gid;
}

// render the Python %s of the timestamp as received
inline std::string ts_as_str(const RawPoint& rp) {
    if (rp.ts_kind == K_STRING) return rp.s.ts_str;
    if (rp.s.ts_num_is_int) return rp.ts_lexeme;
    return py_float_str(rp.s.ts_num);
}

// Validate + normalize one raw point into the batch (mirrors
// add_points_bulk's per-point try block, same error order and strings).
// Returns false -> needs Python fallback for THIS construct.
inline bool finish_point(const RawPoint& rp, PutBatch& out) {
    std::string err, kind;
    int64_t ts_ms = 0;
    double fv = 0;
    int64_t iv = 0;
    bool is_int = false;

    auto fail = [&](const char* k, const std::string& m) {
        out.err_idx.push_back(static_cast<int64_t>(out.ts.size()));
        out.err_msg.push_back(m);
        out.err_kind.push_back(k);
        out.ts.push_back(0);
        out.fval.push_back(0);
        out.ival.push_back(0);
        out.isint.push_back(0);
        out.group.push_back(-1);
        out.span.push_back(rp.span_start);
        out.span.push_back(rp.span_end);
    };

    // 1. missing required fields, in field order
    const char* missing = nullptr;
    if (rp.metric_kind == K_ABSENT || rp.metric_kind == K_NULL ||
        (rp.metric_kind == K_STRING && rp.s.metric.empty()))
        missing = "metric";
    else if (rp.ts_kind == K_ABSENT || rp.ts_kind == K_NULL ||
             rp.ts_kind == K_EMPTY_OBJECT ||
             (rp.ts_kind == K_STRING && rp.s.ts_str.empty()))
        missing = "timestamp";
    else if (rp.val_kind == K_ABSENT || rp.val_kind == K_NULL ||
             rp.val_kind == K_EMPTY_OBJECT ||
             (rp.val_kind == K_STRING && rp.s.val_str.empty()))
        missing = "value";
    else if (rp.tags_kind == K_ABSENT || rp.tags_kind == K_NULL ||
             rp.tags_kind == K_EMPTY_OBJECT)
        missing = "tags";
    if (missing) {
        fail("ValueError", std::string("Missing required field: ") + missing);
        return true;
    }

    // 2. parse_value
    std::string vrepr;
    if (rp.val_kind == K_BOOL) {
        fail("ValueError", std::string("Invalid value: ")
             + (rp.s.val_bool ? "True" : "False"));
        return true;
    } else if (rp.val_kind == K_NUMBER) {
        is_int = rp.s.val_num_is_int;
        if (is_int) {
            iv = rp.s.val_int;
            fv = static_cast<double>(iv);
            vrepr = rp.val_lexeme;
            // normalize "+5" repr to 5 like Python's repr(int)
            if (!vrepr.empty() && vrepr[0] == '+') vrepr = vrepr.substr(1);
            if (rp.s.val_int_overflow) {
                fail("ValueError",
                     "Invalid value, out of long range: " + vrepr);
                return true;
            }
        } else {
            fv = rp.s.val_num;
            // json.loads parses 1e999 to float inf; the Python path
            // rejects it (parse_value: isinf/isnan -> Invalid value)
            if (std::isinf(fv) || std::isnan(fv)) {
                fail("ValueError", "Invalid value: " + py_float_str(fv));
                return true;
            }
        }
    } else {  // string
        std::string text = rp.s.val_str;
        for (char c : text)
            if (static_cast<unsigned char>(c) >= 0x80)
                return false;   // unicode strip semantics: Python path
        size_t a = text.find_first_not_of(" \t\n\r\f\v");
        size_t b = text.find_last_not_of(" \t\n\r\f\v");
        text = (a == std::string::npos) ? "" : text.substr(a, b - a + 1);
        if (!py_repr(rp.s.val_str, vrepr)) return false;
        if (text.empty()) {
            fail("ValueError", "Empty value");
            return true;
        }
        bool of = false;
        if (py_int(text, of, iv)) {
            is_int = true;
            fv = static_cast<double>(iv);
            if (of) {
                fail("ValueError",
                     "Invalid value, out of long range: " + vrepr);
                return true;
            }
        } else {
            double d = 0;
            if (!py_float(text, d)) {
                fail("ValueError", "Invalid value: " + vrepr);
                return true;
            }
            if (std::isnan(d) || std::isinf(d)) {
                fail("ValueError", "Invalid value: " + vrepr);
                return true;
            }
            fv = d;
        }
    }

    // 3. check_timestamp_and_tags: tags presence/count, int(ts) >= 0
    if (rp.s.ntags == 0) {
        fail("ValueError", "Need at least one tag (metric=" + rp.s.metric
             + ", ts=" + ts_as_str(rp) + ")");
        return true;
    }
    if (rp.s.ntags > 8) {
        char buf[80];
        std::snprintf(buf, sizeof buf,
                      "Too many tags: %zu maximum allowed: 8",
                      rp.s.ntags);
        fail("ValueError", buf);
        return true;
    }
    int64_t ts_int = 0;
    if (rp.ts_kind == K_STRING) {
        std::string t = rp.s.ts_str;
        for (char c : t)
            if (static_cast<unsigned char>(c) >= 0x80) return false;
        size_t a = t.find_first_not_of(" \t\n\r\f\v");
        size_t b = t.find_last_not_of(" \t\n\r\f\v");
        std::string stripped =
            (a == std::string::npos) ? "" : t.substr(a, b - a + 1);
        bool of = false;
        if (!py_int(stripped, of, ts_int) || of) {
            if (of) return false;   // arbitrary-precision: Python path
            std::string r;
            if (!py_repr(t, r)) return false;
            fail("ValueError",
                 "invalid literal for int() with base 10: " + r);
            return true;
        }
    } else if (rp.s.ts_num_is_int) {
        ts_int = rp.s.ts_int;
    } else {
        // Beyond int64 the cast is UB and Python's behavior diverges
        // per value (arbitrary-precision ints, OverflowError on inf):
        // the Python path owns those
        if (!(rp.s.ts_num > -9.2e18 && rp.s.ts_num < 9.2e18)) return false;
        ts_int = static_cast<int64_t>(rp.s.ts_num);  // trunc toward zero
    }
    if (ts_int < 0) {
        fail("ValueError", "Invalid timestamp: " + ts_as_str(rp));
        return true;
    }

    // 4. normalize_timestamp_ms
    ts_ms = (ts_int >= SECOND_MASK_LO) ? ts_int : ts_int * 1000;

    // 5. canonical series key: metric + bytewise-sorted tags (index
    //    sort + scratch key buffer: no string copies on the hot path)
    if (rp.s.metric.find_first_of("\x1E\x1F", 0) != std::string::npos ||
        rp.s.metric.find('\0', 0) != std::string::npos)
        return false;
    int32_t gid = assign_group(rp.s.metric, rp.s, out);

    out.ts.push_back(ts_ms);
    out.fval.push_back(fv);
    out.ival.push_back(is_int ? iv : 0);
    out.isint.push_back(is_int ? 1 : 0);
    out.group.push_back(gid);
    out.span.push_back(rp.span_start);
    out.span.push_back(rp.span_end);
    return true;
}

}  // namespace putparse

// -------------------------------------------------------------- C ABI

EXPORT void* eng_put_parse(const char* data, int64_t len) {
    using namespace putparse;
    Parser P(data, static_cast<size_t>(len));
    P.ws();
    if (P.p >= P.end) return nullptr;
    auto* out = new PutBatch();
    out->ts.reserve(static_cast<size_t>(len / 80 + 1));
    RawPoint rp;                 // ONE scratch, reset per point: string
    //                              capacities persist, so a long body
    //                              parses with ~zero per-point allocation
    auto one = [&]() -> bool {
        rp.reset();
        if (!parse_point(P, rp, data)) return false;
        if (P.fallback) return false;
        return finish_point(rp, *out);
    };
    if (*P.p == '[') {
        P.p++;
        bool first = true;
        for (;;) {
            P.ws();
            if (P.p < P.end && *P.p == ']') { P.p++; break; }
            if (!first) {
                if (P.p >= P.end || *P.p != ',') { delete out; return nullptr; }
                P.p++;
            }
            first = false;
            if (!one()) { delete out; return nullptr; }
        }
    } else if (*P.p == '{') {
        if (!one()) { delete out; return nullptr; }
    } else {
        delete out;
        return nullptr;
    }
    P.ws();
    if (P.p != P.end) { delete out; return nullptr; }  // trailing garbage
    return out;
}

EXPORT void eng_put_free(void* h) {
    delete static_cast<putparse::PutBatch*>(h);
}

EXPORT int64_t eng_put_npoints(void* h) {
    return static_cast<int64_t>(
        static_cast<putparse::PutBatch*>(h)->ts.size());
}

EXPORT int64_t eng_put_ngroups(void* h) {
    return static_cast<int64_t>(
        static_cast<putparse::PutBatch*>(h)->gkeys.size());
}

EXPORT const int64_t* eng_put_ts(void* h) {
    return static_cast<putparse::PutBatch*>(h)->ts.data();
}

EXPORT const double* eng_put_fval(void* h) {
    return static_cast<putparse::PutBatch*>(h)->fval.data();
}

EXPORT const int64_t* eng_put_ival(void* h) {
    return static_cast<putparse::PutBatch*>(h)->ival.data();
}

EXPORT const uint8_t* eng_put_isint(void* h) {
    return static_cast<putparse::PutBatch*>(h)->isint.data();
}

EXPORT const int32_t* eng_put_group(void* h) {
    return static_cast<putparse::PutBatch*>(h)->group.data();
}

EXPORT const int64_t* eng_put_spans(void* h) {
    return static_cast<putparse::PutBatch*>(h)->span.data();
}

EXPORT const char* eng_put_group_key(void* h, int64_t g) {
    auto* b = static_cast<putparse::PutBatch*>(h);
    if (g < 0 || static_cast<size_t>(g) >= b->gorig.size()) return nullptr;
    return b->gorig[static_cast<size_t>(g)].c_str();
}

EXPORT int64_t eng_put_nerrors(void* h) {
    return static_cast<int64_t>(
        static_cast<putparse::PutBatch*>(h)->err_idx.size());
}

// j-th error (ascending point index): returns message, sets *point_index
// and *kind
EXPORT const char* eng_put_error(void* h, int64_t j, int64_t* point_index,
                                 const char** kind) {
    auto* b = static_cast<putparse::PutBatch*>(h);
    if (j < 0 || static_cast<size_t>(j) >= b->err_idx.size()) return nullptr;
    *point_index = b->err_idx[static_cast<size_t>(j)];
    *kind = b->err_kind[static_cast<size_t>(j)].c_str();
    return b->err_msg[static_cast<size_t>(j)].c_str();
}

// ============================================================ telnet put
//
// Batch parser for the telnet line protocol's `put` command — the
// reference's primary high-volume ingest path (PutDataPointRpc telnet
// arm, :129).  Input is a block of N complete lines (the server batches
// consecutive put-lines); output reuses PutBatch plus a per-line status
// so exotic lines (non-ASCII, duplicate tags with different values,
// arbitrary-precision numbers) fall back to the per-line Python handler
// INDIVIDUALLY — a weird line costs itself, not the batch.
//
// Line grammar + error strings mirror tsd/rpcs.py exactly:
//   put <metric> <ts> <value> <tag=v>+
//   errors: "not enough arguments (need least 4, got %d)",
//           "invalid timestamp: %s" / int() literal errors, parse_value
//           strings, "invalid tag: %s", "Too many tags: %d ..."

namespace putparse {

enum LineStatus : int8_t {
    LINE_OK = 0,        // columns appended, group assigned
    LINE_ERROR = 1,     // error recorded (telnet-formatted message)
    LINE_FALLBACK = 2,  // python must process this line individually
    LINE_SKIP = 3,      // blank line: no output at all
};

struct TelnetBatch {
    PutBatch batch;                  // columns/groups/errors as for JSON
    std::vector<int8_t> line_status;
    std::vector<int64_t> line_span;  // 2*i: start, 2*i+1: end offsets
    std::vector<int32_t> line_point; // line -> point index or -1
};

// ASCII whitespace only; any byte >= 0x80 in a line forces fallback
// (Python str.split() also splits on unicode whitespace).
inline bool is_ws(char c) {
    return c == ' ' || c == '\t' || c == '\r' || c == '\n' ||
           c == '\f' || c == '\v';
}

// Parse ONE put line [p, q).  Appends to tb.batch on success/error.
inline LineStatus telnet_line(const char* p, const char* q,
                              int64_t span_start, TelnetBatch& tb,
                              RawPoint& rp) {
    PutBatch& out = tb.batch;
    for (const char* c = p; c < q; c++)
        if (static_cast<unsigned char>(*c) >= 0x80) return LINE_FALLBACK;

    // tokenize (Python str.split(): runs of whitespace)
    const char* words[4];        // put, metric, ts, value
    size_t wlen[4];
    size_t nw = 0;
    const char* c = p;
    const char* tag_start = nullptr;
    int extra_words = 0;         // words beyond the first 4 (tags)
    while (c < q) {
        while (c < q && is_ws(*c)) c++;
        if (c >= q) break;
        const char* w0 = c;
        while (c < q && !is_ws(*c)) c++;
        if (nw < 4) {
            words[nw] = w0;
            wlen[nw] = static_cast<size_t>(c - w0);
            nw++;
        } else {
            if (tag_start == nullptr) tag_start = w0;
            extra_words++;
        }
    }
    if (nw == 0) return LINE_SKIP;
    if (wlen[0] != 3 || std::memcmp(words[0], "put", 3) != 0)
        return LINE_FALLBACK;    // not a put line: python handles it

    rp.reset();
    rp.span_start = span_start;
    rp.span_end = span_start + (q - p);

    auto fail = [&](const std::string& m) {
        out.err_idx.push_back(static_cast<int64_t>(out.ts.size()));
        out.err_msg.push_back(m);
        out.err_kind.push_back("ValueError");
        out.ts.push_back(0);
        out.fval.push_back(0);
        out.ival.push_back(0);
        out.isint.push_back(0);
        out.group.push_back(-1);
        out.span.push_back(rp.span_start);
        out.span.push_back(rp.span_end);
        return LINE_ERROR;
    };

    int total_args = static_cast<int>(nw) - 1 + extra_words;
    if (total_args < 4) {
        char buf[72];
        std::snprintf(buf, sizeof buf,
                      "not enough arguments (need least 4, got %d)",
                      total_args);
        return fail(buf);
    }

    // timestamp (parse_telnet_timestamp: float when '.', else int; > 0)
    std::string ts_text(words[2], wlen[2]);
    bool ts_is_float = ts_text.find('.') != std::string::npos;
    double ts_f = 0;
    int64_t ts_i = 0;
    if (ts_is_float) {
        if (!py_float(ts_text, ts_f)) {
            std::string r;
            if (!py_repr(ts_text, r)) return LINE_FALLBACK;
            return fail("could not convert string to float: " + r);
        }
        if (!(ts_f > -9.2e18 && ts_f < 9.2e18)) return LINE_FALLBACK;
        if (ts_f <= 0) return fail("invalid timestamp: " + ts_text);
        ts_i = static_cast<int64_t>(ts_f);
    } else {
        bool of = false;
        if (!py_int(ts_text, of, ts_i)) {
            std::string r;
            if (!py_repr(ts_text, r)) return LINE_FALLBACK;
            return fail("invalid literal for int() with base 10: " + r);
        }
        if (of) return LINE_FALLBACK;   // python arbitrary precision
        if (ts_i <= 0) return fail("invalid timestamp: " + ts_text);
    }

    // tags: re-walk the tail words
    rp.s.ntags = 0;
    c = tag_start;
    while (c != nullptr && c < q) {
        while (c < q && is_ws(*c)) c++;
        if (c >= q) break;
        const char* w0 = c;
        while (c < q && !is_ws(*c)) c++;
        std::string w(w0, c - w0);
        size_t eq = w.find('=');
        if (eq == std::string::npos || eq == 0 || eq + 1 == w.size())
            return fail("invalid tag: " + w);
        if (w.find_first_of("\x1E\x1F", 0) != std::string::npos ||
            w.find('\0', 0) != std::string::npos)
            return LINE_FALLBACK;
        if (rp.s.ntags >= 64) return LINE_FALLBACK;  // bounded dedupe
        if (rp.s.ntags == rp.s.tags.size()) rp.s.tags.emplace_back();
        auto& slot = rp.s.tags[rp.s.ntags];
        slot.first.assign(w, 0, eq);
        slot.second.assign(w, eq + 1, std::string::npos);
        bool dup = false;
        for (size_t ti = 0; ti < rp.s.ntags; ti++) {
            if (rp.s.tags[ti].first == slot.first) {
                if (rp.s.tags[ti].second != slot.second)
                    return LINE_FALLBACK;  // "duplicate tag" repr message
                dup = true;
            }
        }
        if (!dup) rp.s.ntags++;
    }

    // value AFTER tag grammar (python precedence: import_telnet_point
    // runs parse_tags before add_point's parse_value) but BEFORE the
    // tag-count check (which lives in check_timestamp_and_tags, called
    // after parse_value inside _apply_point)
    std::string val_text(words[3], wlen[3]);
    std::string vrepr;
    if (!py_repr(val_text, vrepr)) return LINE_FALLBACK;
    bool is_int = false, vof = false;
    int64_t iv = 0;
    double fv = 0;
    if (py_int(val_text, vof, iv)) {
        is_int = true;
        if (vof) return LINE_FALLBACK;  // store-side OverflowError path
        fv = static_cast<double>(iv);
    } else {
        if (!py_float(val_text, fv))
            return fail("Invalid value: " + vrepr);
        if (std::isnan(fv) || std::isinf(fv))
            return fail("Invalid value: " + vrepr);
    }

    if (rp.s.ntags > 8) {
        char buf[80];
        std::snprintf(buf, sizeof buf,
                      "Too many tags: %zu maximum allowed: 8", rp.s.ntags);
        return fail(buf);
    }

    // canonical key + columns (same as the JSON path's step 5)
    std::string metric(words[1], wlen[1]);
    if (metric.find_first_of("\x1E\x1F", 0) != std::string::npos ||
        metric.find('\0', 0) != std::string::npos)
        return LINE_FALLBACK;
    int32_t gid = assign_group(metric, rp.s, out);
    int64_t ts_ms = (ts_i >= SECOND_MASK_LO) ? ts_i : ts_i * 1000;
    out.ts.push_back(ts_ms);
    out.fval.push_back(fv);
    out.ival.push_back(is_int ? iv : 0);
    out.isint.push_back(is_int ? 1 : 0);
    out.group.push_back(gid);
    out.span.push_back(rp.span_start);
    out.span.push_back(rp.span_end);
    return LINE_OK;
}

}  // namespace putparse

EXPORT void* eng_telnet_parse(const char* data, int64_t len) {
    using namespace putparse;
    auto* tb = new TelnetBatch();
    tb->batch.ts.reserve(static_cast<size_t>(len / 40 + 1));
    RawPoint rp;
    const char* p = data;
    const char* end = data + len;
    while (p < end) {
        const char* nl = static_cast<const char*>(
            std::memchr(p, '\n', static_cast<size_t>(end - p)));
        const char* q = nl ? nl : end;
        int64_t start = p - data;
        size_t pt_before = tb->batch.ts.size();
        LineStatus st = telnet_line(p, q, start, *tb, rp);
        if (st != LINE_SKIP) {
            tb->line_status.push_back(st);
            tb->line_span.push_back(start);
            tb->line_span.push_back(q - data);
            tb->line_point.push_back(
                st == LINE_FALLBACK
                    ? -1 : static_cast<int32_t>(pt_before));
        }
        p = nl ? nl + 1 : end;
    }
    return tb;
}

EXPORT void eng_telnet_free(void* h) {
    delete static_cast<putparse::TelnetBatch*>(h);
}

EXPORT void* eng_telnet_batch(void* h) {   // the embedded PutBatch view
    return &static_cast<putparse::TelnetBatch*>(h)->batch;
}

EXPORT int64_t eng_telnet_nlines(void* h) {
    return static_cast<int64_t>(
        static_cast<putparse::TelnetBatch*>(h)->line_status.size());
}

EXPORT const int8_t* eng_telnet_status(void* h) {
    return static_cast<putparse::TelnetBatch*>(h)->line_status.data();
}

EXPORT const int64_t* eng_telnet_spans(void* h) {
    return static_cast<putparse::TelnetBatch*>(h)->line_span.data();
}

EXPORT const int32_t* eng_telnet_point(void* h) {
    return static_cast<putparse::TelnetBatch*>(h)->line_point.data();
}

// ------------------------------------------------------------ answer text
//
// The points of a grouped /api/query answer as the JSON text json.dumps
// writes for them (query/planner.py QueryResult.json_text): every finite
// double as Python's float.__repr__ gives it.  Both that and
// std::to_chars without a precision write the shortest digits that read
// back to the same double, the nearest of them to it; only the layout
// differs, and py_float_repr moves to_chars' scientific form into it.

namespace answertext {

// A finite double's repr: plain notation where its decimal exponent
// lies in [-4, 16), "d[.ddd]e±XX" outside, which is to_chars'
// scientific form as it stands.  Writes at most 24 bytes at `out`, the
// mantissa first where to_chars put it; returns the end.
inline char* py_float_repr(double v, char* out) {
    char* end = std::to_chars(out, out + 24, v,
                              std::chars_format::scientific).ptr;
    char* e = end[-4] == 'e' ? end - 4 : end - 5;   // 2 or 3 digits
    int exp10 = 0;
    for (const char* d = e + 2; d < end; d++) exp10 = exp10 * 10 + (*d - '0');
    if (e[1] == '-') exp10 = -exp10;
    if (exp10 < -4 || exp10 >= 16) return end;
    char* lead = out + (*out == '-');   // the first digit
    char digits[20];
    int n = 1;
    digits[0] = *lead;
    for (const char* d = lead + 2; d < e; d++) digits[n++] = *d;
    int point = exp10 + 1;     // digits before the decimal point
    char* w = lead;
    if (point <= 0) {
        *w++ = '0';
        *w++ = '.';
        for (int i = point; i < 0; i++) *w++ = '0';
        std::memcpy(w, digits, n);
        return w + n;
    }
    if (point < n) {
        std::memcpy(w, digits, point);
        w += point;
        *w++ = '.';
        std::memcpy(w, digits + point, n - point);
        return w + (n - point);
    }
    std::memcpy(w, digits, n);
    w += n;
    for (int i = n; i < point; i++) *w++ = '0';
    *w++ = '.';
    *w++ = '0';
    return w;
}

}  // namespace answertext

// Rows `rows[0..nrows)` of the C-ordered [*, ncols] float64 `block`,
// each as `piece[0] v0 piece[1] v1 ... piece[ncols-1] v(ncols-1)
// piece[ncols]`, one after another into `out`; piece i is
// pieces[piece_off[i] .. piece_off[i+1]).  offsets[i] is where row i
// starts, offsets[nrows] the end.  Returns the bytes written, -1 where
// `cap` would be exceeded, -2 at a NaN or an infinity.
EXPORT int64_t eng_emit_rows(const double* block, int64_t ncols,
                             const int64_t* rows, int64_t nrows,
                             const char* pieces, const int64_t* piece_off,
                             char* out, int64_t cap, int64_t* offsets) {
    const int64_t row_max = piece_off[ncols + 1] + 24 * ncols;
    int64_t pos = 0;
    for (int64_t i = 0; i < nrows; i++) {
        offsets[i] = pos;
        if (cap - pos < row_max) return -1;
        const double* v = block + rows[i] * ncols;
        char* w = out + pos;
        for (int64_t j = 0; j < ncols; j++) {
            if (!std::isfinite(v[j])) return -2;
            int64_t len = piece_off[j + 1] - piece_off[j];
            std::memcpy(w, pieces + piece_off[j], len);
            w = answertext::py_float_repr(v[j], w + len);
        }
        int64_t tail = piece_off[ncols + 1] - piece_off[ncols];
        std::memcpy(w, pieces + piece_off[ncols], tail);
        pos = (w + tail) - out;
    }
    offsets[nrows] = pos;
    return pos;
}
