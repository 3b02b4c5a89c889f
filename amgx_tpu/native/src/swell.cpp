// Host construction of the windowed-ELL (SWELL) layout
// (ops/pallas_swell.py) — the storage of the Pallas TPU gather SpMV for
// unstructured matrices (the csrmv analog, src/multiply.cu:74-121).
//
// The numpy formulation costs seconds per hierarchy at 64^3 scale
// (reduceat window scans + giant fancy-index scatters); these are the
// same sweeps as single O(nnz) passes.
//
// Layout contract (must match build_swell_host): rows tile into
// super-blocks of 1024 (8 sublane groups x 128 lanes); per block the
// column window starts at c0 = (min col // 128) * 128; entries store
// slot-major as (nb, 8, kpad, 128) with local columns ci - c0; beside
// them each row group's list of the window chunks it has a column in.
#include <algorithm>
#include <cstdint>
#include <vector>

namespace {
constexpr int32_t LANES = 128;
constexpr int32_t SUBS = 8;
constexpr int32_t BLOCK_ROWS = SUBS * LANES;
}  // namespace

extern "C" {

// Per-super-block window scan. Writes c0row[nb] (window start in
// 128-rows); *out_kmax gets the max row length. Returns the max window
// width in 128-chunks (w128), 0 when the matrix has no entries.
int32_t amgx_swell_windows(
    int32_t n, const int32_t* ro, const int32_t* ci,
    int32_t* c0row, int32_t* out_kmax) {
    const int32_t nb = (n + BLOCK_ROWS - 1) / BLOCK_ROWS;
    int32_t kmax = 0, w128 = 0;
    for (int32_t b = 0; b < nb; ++b) {
        const int32_t r0 = b * BLOCK_ROWS;
        const int32_t r1 = std::min(n, r0 + BLOCK_ROWS);
        int32_t bmin = INT32_MAX, bmax = -1;
        for (int32_t i = r0; i < r1; ++i) {
            const int32_t len = ro[i + 1] - ro[i];
            if (len > kmax) kmax = len;
            for (int32_t e = ro[i]; e < ro[i + 1]; ++e) {
                const int32_t c = ci[e];
                if (c < bmin) bmin = c;
                if (c > bmax) bmax = c;
            }
        }
        if (bmax < 0) { bmin = 0; bmax = 0; }  // empty block
        const int32_t c0 = (bmin / LANES) * LANES;
        const int32_t span = bmax - c0 + 1;
        const int32_t chunks = (span + LANES - 1) / LANES;
        c0row[b] = c0 / LANES;
        if (chunks > w128) w128 = chunks;
    }
    *out_kmax = kmax;
    return w128;
}

// Each row group's chunk list: for every 128 rows, the distinct
// 128-column chunks of their block's window (local to its c0) in which
// they have a column, ascending. The kernels' gather runs group by
// group over these lists alone: a block of a coarse operator on a 3-D
// grid touches a few bands of its span, and a group a sixth to a
// quarter of the chunks its block does (PR 48; a bit an 8-chunk slab of
// the block's span, PR 47's form, visited five times the vregs).
// counts[nb * 8] gets each group's length, `flat` (room for
// min(nnz, nb * 8 * w128) entries) the lists back to back; the padded
// (nb, 8, 1 + L) layout is pallas_swell.pad_chunk_lists's. Returns the
// entries written.
int64_t amgx_swell_chunklists(
    int32_t n, const int32_t* ro, const int32_t* ci, const int32_t* c0row,
    int32_t w128, int32_t* counts, int32_t* flat) {
    std::vector<int32_t> seen(w128, -1);     // the group that last hit
    int64_t out = 0;
    const int32_t ngroups = (n + LANES - 1) / LANES;
    for (int32_t g = 0; g < ngroups; ++g) {
        const int32_t c0 = c0row[g / SUBS] * LANES;
        const int32_t r1 = std::min(n, (g + 1) * LANES);
        int32_t lo = w128, hi = -1;
        for (int32_t e = ro[g * LANES]; e < ro[r1]; ++e) {
            const int32_t c = (ci[e] - c0) / LANES;
            seen[c] = g;
            if (c < lo) lo = c;
            if (c > hi) hi = c;
        }
        const int64_t first = out;
        for (int32_t c = lo; c <= hi; ++c)
            if (seen[c] == g) flat[out++] = c;
        counts[g] = static_cast<int32_t>(out - first);
    }
    return out;
}

// What the row-split form A = S A' at piece length K would list,
// from the pattern alone and in one pass, with no array of A' built
// (pallas_swell._split_counts; the layout choice counts a few K an
// operator, PR 51): piece j of row i is the entries
// [ro_i + j K, ro_i + (j + 1) K), a row of A'; 128 consecutive pieces
// are a row group of A', 1,024 a block. A block's window starts on a
// multiple of 128, so the distinct chunks a group lists are the
// distinct ci / 128 of its entries, whatever the window's start. S has
// row i's pieces as adjacent columns, so a group of 128 rows lists the
// chunks from its first piece's to its last's. out[6]: rows of A', its
// longest row, its widest block window in chunks, its listed chunks,
// the longest row of S, S's listed chunks.
void amgx_swell_split_count(
    int32_t n, const int32_t* ro, const int32_t* ci, int32_t K,
    int64_t* out) {
    std::vector<int64_t> seen;               // the group that last hit
    int64_t p = 0, listed_a = 0, listed_s = 0, group_first = 0;
    int32_t kmax_a = 0, kmax_s = 0, w128 = 0;
    int32_t bmin = INT32_MAX, bmax = -1;
    auto close_block = [&]() {
        if (bmax < 0) { bmin = 0; bmax = 0; }
        const int32_t c0 = (bmin / LANES) * LANES;
        w128 = std::max(w128, (bmax - c0 + 1 + LANES - 1) / LANES);
        bmin = INT32_MAX; bmax = -1;
    };
    for (int32_t i = 0; i < n; ++i) {
        if (i % LANES == 0) group_first = p;
        const int32_t len = ro[i + 1] - ro[i];
        const int32_t pieces = (len + K - 1) / K;
        kmax_s = std::max(kmax_s, pieces);
        for (int32_t j = 0; j < pieces; ++j, ++p) {
            if (p % BLOCK_ROWS == 0 && p > 0) close_block();
            const int64_t g = p / LANES;
            const int32_t e0 = ro[i] + j * K;
            const int32_t e1 = std::min(e0 + K, ro[i + 1]);
            kmax_a = std::max(kmax_a, e1 - e0);
            for (int32_t e = e0; e < e1; ++e) {
                const int32_t c = ci[e];
                bmin = std::min(bmin, c);
                bmax = std::max(bmax, c);
                const size_t ch = static_cast<size_t>(c / LANES);
                if (ch >= seen.size()) seen.resize(ch + 1, -1);
                if (seen[ch] != g) { seen[ch] = g; ++listed_a; }
            }
        }
        if ((i % LANES == LANES - 1 || i == n - 1) && p > group_first)
            listed_s += (p - 1) / LANES - group_first / LANES + 1;
    }
    if (p > 0) close_block();
    out[0] = p; out[1] = kmax_a; out[2] = w128; out[3] = listed_a;
    out[4] = kmax_s; out[5] = listed_s;
}

// Scatter entries into caller-zeroed (nb, 8, kpad, 128) slot-major
// buffers. Local column = ci - c0row[block] * 128.
#define SWELL_FILL(name, T)                                              \
    void name(int32_t n, int32_t kpad, const int32_t* ro,                \
              const int32_t* ci, const T* vals, const int32_t* c0row,    \
              int32_t* cols4, T* vals4) {                                \
        for (int32_t i = 0; i < n; ++i) {                                \
            const int32_t b = i / BLOCK_ROWS;                            \
            const int32_t sub = (i % BLOCK_ROWS) / LANES;                \
            const int32_t lane = i & (LANES - 1);                        \
            const int32_t c0 = c0row[b] * LANES;                         \
            const int64_t base =                                         \
                ((static_cast<int64_t>(b) * SUBS + sub) * kpad) * LANES  \
                + lane;                                                  \
            int64_t slot = 0;                                            \
            for (int32_t e = ro[i]; e < ro[i + 1]; ++e, ++slot) {        \
                const int64_t t = base + slot * LANES;                   \
                cols4[t] = ci[e] - c0;                                   \
                vals4[t] = vals[e];                                      \
            }                                                            \
        }                                                                \
    }

SWELL_FILL(amgx_swell_fill_f64, double)
SWELL_FILL(amgx_swell_fill_f32, float)

// Values-only re-scatter (replace_coefficients with structure reuse).
#define SWELL_REFILL(name, T)                                            \
    void name(int32_t n, int32_t kpad, const int32_t* ro, const T* vals, \
              T* vals4) {                                                \
        for (int32_t i = 0; i < n; ++i) {                                \
            const int32_t b = i / BLOCK_ROWS;                            \
            const int32_t sub = (i % BLOCK_ROWS) / LANES;                \
            const int64_t base =                                         \
                ((static_cast<int64_t>(b) * SUBS + sub) * kpad) * LANES  \
                + (i & (LANES - 1));                                     \
            int64_t slot = 0;                                            \
            for (int32_t e = ro[i]; e < ro[i + 1]; ++e, ++slot)          \
                vals4[base + slot * LANES] = vals[e];                    \
        }                                                                \
    }

SWELL_REFILL(amgx_swell_refill_f64, double)
SWELL_REFILL(amgx_swell_refill_f32, float)

}  // extern "C"
