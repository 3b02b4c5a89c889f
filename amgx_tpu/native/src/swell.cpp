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
