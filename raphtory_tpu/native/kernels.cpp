// raphtory_tpu native runtime kernels.
//
// The reference's performance-critical host layer is the JVM/Akka actor
// runtime (SURVEY §2.9); here the host hot loops around the TPU compute path
// are native C++: the snapshot-builder's event sorts (the graph-builder), the
// sorted two-column join used by property materialisation, and the ingest
// CSV tokeniser (the data-loader). Loaded from Python via ctypes
// (`raphtory_tpu/native/lib.py`); every entry point has a pure-numpy
// fallback, so this library is an accelerator, not a dependency.
//
// Build: g++ -O3 -shared -fPIC (see native/build.py). Plain C ABI.

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

extern "C" {

// Argsort event rows by (k1[, k2], time, alive-first) — the order
// np.lexsort((~alive, times, k2, k1)) produces. At equal (key, time) dead
// rows sort last so a "last row of group" scan picks the tombstone
// (delete-wins tie-break of the temporal fold; Entity.scala:41-57 semantics).
// k2 may be null for single-key streams. order_out: int64[n].
void rtpu_sort_events(int64_t n, const int64_t* k1, const int64_t* k2,
                      const int64_t* times, const uint8_t* alive,
                      int64_t* order_out) {
    for (int64_t i = 0; i < n; ++i) order_out[i] = i;
    if (k2 != nullptr) {
        std::sort(order_out, order_out + n, [&](int64_t a, int64_t b) {
            if (k1[a] != k1[b]) return k1[a] < k1[b];
            if (k2[a] != k2[b]) return k2[a] < k2[b];
            if (times[a] != times[b]) return times[a] < times[b];
            return alive[a] > alive[b];
        });
    } else {
        std::sort(order_out, order_out + n, [&](int64_t a, int64_t b) {
            if (k1[a] != k1[b]) return k1[a] < k1[b];
            if (times[a] != times[b]) return times[a] < times[b];
            return alive[a] > alive[b];
        });
    }
}

// Fused group fold over rows already sorted by rtpu_sort_events: one output
// row per distinct key with (latest_time, latest_alive, first_time) — the
// whole _fold_latest in one pass. Returns the group count.
int64_t rtpu_fold_sorted(int64_t n, const int64_t* k1, const int64_t* k2,
                         const int64_t* times, const uint8_t* alive,
                         const int64_t* order,
                         int64_t* out_k1, int64_t* out_k2,
                         int64_t* out_latest_t, uint8_t* out_alive,
                         int64_t* out_first_t) {
    int64_t g = -1;
    for (int64_t i = 0; i < n; ++i) {
        int64_t r = order[i];
        bool fresh = (g < 0) || k1[r] != out_k1[g] ||
                     (k2 != nullptr && k2[r] != out_k2[g]);
        if (fresh) {
            ++g;
            out_k1[g] = k1[r];
            if (k2 != nullptr) out_k2[g] = k2[r];
            out_first_t[g] = times[r];
        }
        out_latest_t[g] = times[r];
        out_alive[g] = alive[r];
    }
    return g + 1;
}

// Position of each (q1, q2) pair in key columns sorted lexicographically by
// (b1, b2); -1 when absent. Replaces the per-query Python loop in
// snapshot._lex_lookup (edge-property materialisation hot path).
void rtpu_lex_lookup2(int64_t nb, const int64_t* b1, const int64_t* b2,
                      int64_t nq, const int64_t* q1, const int64_t* q2,
                      int64_t* out) {
    for (int64_t i = 0; i < nq; ++i) {
        const int64_t* lo = std::lower_bound(b1, b1 + nb, q1[i]);
        const int64_t* hi = std::upper_bound(lo, b1 + nb, q1[i]);
        if (lo == hi) { out[i] = -1; continue; }
        int64_t l = lo - b1, h = hi - b1;
        const int64_t* p = std::lower_bound(b2 + l, b2 + h, q2[i]);
        out[i] = (p != b2 + h && *p == q2[i]) ? (p - b2) : -1;
    }
}

// CSV integer-column tokeniser: extract up to `ncols` int64 columns (by
// 0-based column index, ascending) from a newline-separated byte buffer.
// Rows with missing/non-numeric cells are skipped. Returns rows written.
// outs: ncols pointers worth of int64[max_rows] laid out contiguously as
// out[c * max_rows + row].
int64_t rtpu_parse_int_csv(const char* buf, int64_t len, char sep,
                           const int64_t* cols, int64_t ncols,
                           int64_t* out, int64_t max_rows) {
    int64_t row = 0;
    const char* p = buf;
    const char* end = buf + len;
    int64_t vals[16];
    while (p < end && row < max_rows) {
        const char* line_end = static_cast<const char*>(
            memchr(p, '\n', end - p));
        if (!line_end) line_end = end;
        int64_t col = 0, want = 0;
        bool ok = true;
        const char* q = p;
        while (want < ncols && q <= line_end) {
            const char* cell_end = q;
            while (cell_end < line_end && *cell_end != sep) ++cell_end;
            if (col == cols[want]) {
                // Parse int64 exactly like Python's int(cell): optional
                // sign, digits only, surrounding whitespace tolerated
                // (includes the \r of CRLF files). Anything else — floats,
                // empty cells — rejects the row, matching the row path.
                const char* c = q;
                const char* ce = cell_end;
                while (c < ce && (*c == ' ' || *c == '\t')) ++c;
                while (ce > c && (ce[-1] == ' ' || ce[-1] == '\t' ||
                                  ce[-1] == '\r')) --ce;
                bool neg = false;
                if (c < ce && (*c == '-' || *c == '+')) {
                    neg = (*c == '-');
                    ++c;
                }
                if (c == ce || *c < '0' || *c > '9') { ok = false; break; }
                int64_t v = 0;
                // digits with Python-style single '_' grouping: an
                // underscore is legal only BETWEEN two digits (int("1_0")
                // == 10; "_1", "1_", "1__0" all reject) — keeps the bulk
                // path row-for-row identical to the int() row path.
                while (c < ce) {
                    if (*c >= '0' && *c <= '9') {
                        v = v * 10 + (*c++ - '0');
                    } else if (*c == '_' && c + 1 < ce &&
                               c[1] >= '0' && c[1] <= '9') {
                        ++c;
                    } else {
                        break;
                    }
                }
                if (c != ce) { ok = false; break; }
                vals[want++] = neg ? -v : v;
            }
            ++col;
            if (cell_end == line_end) break;
            q = cell_end + 1;
        }
        if (ok && want == ncols) {
            for (int64_t c2 = 0; c2 < ncols; ++c2)
                out[c2 * max_rows + row] = vals[c2];
            ++row;
        }
        p = line_end + 1;
    }
    return row;
}


// ---------------------------------------------------------------- bulk load

// Parallel stable LSD radix argsort of uint64 keys. The bulk-load hot sort:
// 100M keys in seconds where std::sort takes minutes. Stability preserves
// the caller's time order within equal keys (the (pair, time) trick the
// bulk loader relies on). order_out: int64[n].

void rtpu_radix_argsort_u64(int64_t n, const uint64_t* keys,
                            int64_t* order_out) {
    const int PASSES = 8, BUCKETS = 256;
    int nt = (int)std::thread::hardware_concurrency();
    if (nt < 1) nt = 1;
    if (nt > 32) nt = 32;
    if (n < (1 << 16)) nt = 1;

    std::vector<uint64_t> kbuf(n);
    std::vector<int64_t> obuf(n);
    std::vector<uint64_t> kbuf2(n);
    std::vector<int64_t> obuf2(n);
    for (int64_t i = 0; i < n; ++i) { kbuf[i] = keys[i]; obuf[i] = i; }

    uint64_t* ks = kbuf.data(); int64_t* os = obuf.data();
    uint64_t* kd = kbuf2.data(); int64_t* od = obuf2.data();

    std::vector<int64_t> hist((size_t)nt * BUCKETS);
    int64_t chunk = (n + nt - 1) / nt;

    for (int pass = 0; pass < PASSES; ++pass) {
        int shift = pass * 8;
        // skip passes whose byte is constant (common: high bytes of ids)
        std::fill(hist.begin(), hist.end(), 0);
        auto count = [&](int t) {
            int64_t lo = t * chunk, hi = std::min(n, lo + chunk);
            int64_t* h = &hist[(size_t)t * BUCKETS];
            for (int64_t i = lo; i < hi; ++i)
                ++h[(ks[i] >> shift) & 0xff];
        };
        {
            std::vector<std::thread> th;
            for (int t = 1; t < nt; ++t) th.emplace_back(count, t);
            count(0);
            for (auto& x : th) x.join();
        }
        int nonzero = 0; int64_t first_total = 0;
        for (int b = 0; b < BUCKETS && nonzero <= 1; ++b) {
            int64_t tot = 0;
            for (int t = 0; t < nt; ++t) tot += hist[(size_t)t * BUCKETS + b];
            if (tot) { ++nonzero; first_total = tot; }
        }
        if (nonzero <= 1 && first_total == n) continue;  // constant byte
        // exclusive prefix, bucket-major then thread order (stability)
        int64_t run = 0;
        for (int b = 0; b < BUCKETS; ++b) {
            for (int t = 0; t < nt; ++t) {
                int64_t c = hist[(size_t)t * BUCKETS + b];
                hist[(size_t)t * BUCKETS + b] = run;
                run += c;
            }
        }
        auto scatter = [&](int t) {
            int64_t lo = t * chunk, hi = std::min(n, lo + chunk);
            int64_t* h = &hist[(size_t)t * BUCKETS];
            for (int64_t i = lo; i < hi; ++i) {
                int64_t p = h[(ks[i] >> shift) & 0xff]++;
                kd[p] = ks[i]; od[p] = os[i];
            }
        };
        {
            std::vector<std::thread> th;
            for (int t = 1; t < nt; ++t) th.emplace_back(scatter, t);
            scatter(0);
            for (auto& x : th) x.join();
        }
        std::swap(ks, kd); std::swap(os, od);
    }
    std::memcpy(order_out, os, (size_t)n * sizeof(int64_t));
}

// Parallel batched lower/upper bound over a sorted u64 array — the per-hop
// latest-event lookup of the bulk loader (100M queries/hop).
// side: 0 = left (lower_bound), 1 = right (upper_bound). out: int64[nq].
void rtpu_searchsorted_u64(int64_t nb, const uint64_t* base,
                           int64_t nq, const uint64_t* queries,
                           int32_t side, int64_t* out) {
    int nt = (int)std::thread::hardware_concurrency();
    if (nt < 1) nt = 1;
    if (nt > 32) nt = 32;
    if (nq < (1 << 14)) nt = 1;
    int64_t chunk = (nq + nt - 1) / nt;
    auto work = [&](int t) {
        int64_t lo = t * chunk, hi = std::min(nq, lo + chunk);
        for (int64_t i = lo; i < hi; ++i) {
            const uint64_t* p = side
                ? std::upper_bound(base, base + nb, queries[i])
                : std::lower_bound(base, base + nb, queries[i]);
            out[i] = (int64_t)(p - base);
        }
    };
    std::vector<std::thread> th;
    for (int t = 1; t < nt; ++t) th.emplace_back(work, t);
    work(0);
    for (auto& x : th) x.join();
}

// Triangles of an undirected graph whose every edge points from its end of
// lower rank to the other: CSR ``off[n + 1]`` / ``nbr[U]``, a vertex's
// out-neighbours ascending, an edge's id its CSR position. Each triangle
// v1 < v2 < v3 is found once, from v1, by merging what is left of N+(v1)
// after v2 with N+(v2): e1 = (v1, v2), e2 = (v1, v3), e3 = (v2, v3), rows
// ascending by (e1, e2). ``row_off == nullptr`` is the counting pass
// (``cnt[v1]`` = triangles whose lowest vertex is v1); with ``row_off`` (the
// exclusive running sum of ``cnt``) the rows are written. Vertices are handed
// to the threads in small blocks, so a hub's block stalls nobody.
void rtpu_triangles(int64_t n, const int64_t* off, const int32_t* nbr,
                    int64_t* cnt, const int64_t* row_off,
                    int32_t* e1, int32_t* e2, int32_t* e3) {
    int nt = (int)std::thread::hardware_concurrency();
    if (nt < 1) nt = 1;
    if (nt > 32) nt = 32;
    if (off[n] < (1 << 18)) nt = 1;   // a small graph is done before a thread starts
    const int64_t block = 64;
    std::atomic<int64_t> next(0);
    auto work = [&]() {
        for (;;) {
            int64_t lo = next.fetch_add(block), hi = std::min(n, lo + block);
            if (lo >= n) return;
            for (int64_t v1 = lo; v1 < hi; ++v1) {
                const int64_t end1 = off[v1 + 1];
                int64_t found = 0, at = row_off ? row_off[v1] : 0;
                for (int64_t i = off[v1]; i < end1; ++i) {
                    const int32_t v2 = nbr[i];
                    int64_t j = i + 1, k = off[v2];
                    const int64_t end2 = off[v2 + 1];
                    while (j < end1 && k < end2) {
                        const int32_t a = nbr[j], b = nbr[k];
                        if (a < b) ++j;
                        else if (b < a) ++k;
                        else {
                            if (row_off) {
                                e1[at] = (int32_t)i; e2[at] = (int32_t)j;
                                e3[at] = (int32_t)k; ++at;
                            }
                            ++found; ++j; ++k;
                        }
                    }
                }
                if (!row_off) cnt[v1] = found;
            }
        }
    };
    std::vector<std::thread> th;
    for (int t = 1; t < nt; ++t) th.emplace_back(work);
    work();
    for (auto& x : th) x.join();
}

}  // extern "C"
