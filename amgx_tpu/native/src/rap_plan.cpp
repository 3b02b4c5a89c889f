// Plan-split Galerkin RAP: one STAGE of the structure phase.
//
// A stage of ops/spgemm.py build_rap_plan expands the candidates of
// C = A @ B from the two patterns (every entry e of A's row i pairs
// with every entry f of B's row a_ci[e]) and coalesces them in the
// stable (row, column) order. The numpy form materialises five int64
// arrays of candidate length and lexsorts them: 56 bytes and two
// stable sorts a candidate, most of a classical set-up's wall where
// coarse rows are long. Rows arrive in order, so the lexsort is a
// stable sort BY COLUMN WITHIN EACH ROW, which this sweep does row by
// row on a 64-bit key (column << 32 | position in the row's candidate
// list): the same order to the last tie, 12 bytes a candidate.
//
//   amgx_rap_plan_stage_count   cum[i] = candidates before row i;
//                               returns the total
//   amgx_rap_plan_stage_fill    sa, sb: the A and B entry of each
//                               candidate in coalesce order; seg: its
//                               segment (output entry); urow[i]: the
//                               output entries of row i; returns the
//                               number of output entries
#include <algorithm>
#include <cstddef>
#include <cstdint>
#include <vector>

extern "C" {

long long amgx_rap_plan_stage_count(
    int32_t n, const int64_t* a_ro, const int32_t* a_ci,
    const int64_t* b_ro, int64_t* cum) {
    int64_t total = 0;
    for (int32_t i = 0; i < n; ++i) {
        cum[i] = total;
        for (int64_t e = a_ro[i]; e < a_ro[i + 1]; ++e) {
            total += b_ro[a_ci[e] + 1] - b_ro[a_ci[e]];
        }
    }
    cum[n] = total;
    return total;
}

long long amgx_rap_plan_stage_fill(
    int32_t n, const int64_t* a_ro, const int32_t* a_ci,
    const int64_t* b_ro, const int32_t* b_ci, const int64_t* cum,
    int32_t* sa, int32_t* sb, int32_t* seg, int32_t* urow) {
    std::vector<uint64_t> keys;
    std::vector<int32_t> ea, eb;
    int64_t n_u = 0;
    for (int32_t i = 0; i < n; ++i) {
        const size_t m = static_cast<size_t>(cum[i + 1] - cum[i]);
        keys.resize(m);
        ea.resize(m);
        eb.resize(m);
        size_t t = 0;
        for (int64_t e = a_ro[i]; e < a_ro[i + 1]; ++e) {
            const int32_t k = a_ci[e];
            for (int64_t f = b_ro[k]; f < b_ro[k + 1]; ++f, ++t) {
                keys[t] = (static_cast<uint64_t>(
                               static_cast<uint32_t>(b_ci[f])) << 32)
                          | static_cast<uint64_t>(t);
                ea[t] = static_cast<int32_t>(e);
                eb[t] = static_cast<int32_t>(f);
            }
        }
        std::sort(keys.begin(), keys.end());
        int32_t* out_a = sa + cum[i];
        int32_t* out_b = sb + cum[i];
        int32_t* out_s = seg + cum[i];
        int32_t here = 0;
        uint32_t last = 0;
        for (size_t s = 0; s < m; ++s) {
            const uint32_t col = static_cast<uint32_t>(keys[s] >> 32);
            const size_t src = static_cast<size_t>(keys[s] & 0xffffffffu);
            if (s == 0 || col != last) {
                ++here;
                ++n_u;
                last = col;
            }
            out_a[s] = ea[src];
            out_b[s] = eb[src];
            out_s[s] = static_cast<int32_t>(n_u - 1);
        }
        urow[i] = here;
    }
    return n_u;
}

}  // extern "C"
