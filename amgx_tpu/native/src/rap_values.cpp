// Plan-split Galerkin RAP: the VALUES-ONLY numeric sweep.
//
// The structure phase (ops/spgemm.py RapPlan) has already fixed the
// expansion gather indices, the lexsorted coalesce order and the
// per-entry segment boundaries, so — unlike amgx_rap_build's
// Gustavson sweep (rap.cpp), which rediscovers the output pattern
// with stamp/accumulator bookkeeping on every call — this sweep is
// two flat passes of pure fused multiply-adds through precomputed
// indices. This is the host-route payoff of the symbolic/numeric
// split: a warm setup or value resetup pays only this.
//
//   stage 1 (optional): t[k]   = sum_{e in [s1[k], s1[k+1])}
//                                    a[sa[e]] * p[sp[e]]
//   stage 2:            out[u] = sum_{f in [s2[u], s2[u+1])}
//                                    (r[sr[f]] *) base[st[f]]
//
// base = t (two-stage triple product) or a itself (the aggregation
// relabel form, has_stage1 = 0). Summation is strict left-to-right
// per segment, matching the numpy reduceat fallback's short-segment
// order.
#include <cstddef>
#include <cstdint>
#include <vector>

namespace {

// One sweep for both precisions: products and sums are made in T, so
// the float32 form rounds where numpy's float32 reduceat route rounds.
template <typename T>
int32_t plan_values(
    int64_t n_t, const int32_t* sa, const int32_t* sp,
    const int32_t* s1,
    int64_t n_u, const int32_t* sr, const int32_t* st,
    const int32_t* s2,
    const T* a, const T* p, const T* r,
    int32_t has_stage1, int32_t has_r, T* out) {
    std::vector<T> t_buf;
    const T* base = a;
    if (has_stage1) {
        t_buf.resize(static_cast<size_t>(n_t));
        for (int64_t k = 0; k < n_t; ++k) {
            T acc = 0;
            for (int32_t e = s1[k]; e < s1[k + 1]; ++e) {
                acc += a[sa[e]] * p[sp[e]];
            }
            t_buf[static_cast<size_t>(k)] = acc;
        }
        base = t_buf.data();
    }
    if (has_r) {
        for (int64_t u = 0; u < n_u; ++u) {
            T acc = 0;
            for (int32_t f = s2[u]; f < s2[u + 1]; ++f) {
                acc += r[sr[f]] * base[st[f]];
            }
            out[u] = acc;
        }
    } else {
        for (int64_t u = 0; u < n_u; ++u) {
            T acc = 0;
            for (int32_t f = s2[u]; f < s2[u + 1]; ++f) {
                acc += base[st[f]];
            }
            out[u] = acc;
        }
    }
    return 0;
}

}  // namespace

extern "C" {

// Segment boundaries arrive int32 (candidate totals are guarded
// < 2^31 by the plan builders, and the int32 form halves the plan's
// index memory at 128^3 scale).
int32_t amgx_rap_plan_values(
    int64_t n_t, const int32_t* sa, const int32_t* sp,
    const int32_t* s1,
    int64_t n_u, const int32_t* sr, const int32_t* st,
    const int32_t* s2,
    const double* a, const double* p, const double* r,
    int32_t has_stage1, int32_t has_r, double* out) {
    return plan_values<double>(n_t, sa, sp, s1, n_u, sr, st, s2, a, p, r,
                               has_stage1, has_r, out);
}

// The float32 twin: a dFFI hierarchy built on the host (every classical
// one on an accelerator) re-values its levels through this at every
// structure resetup.
int32_t amgx_rap_plan_values_f32(
    int64_t n_t, const int32_t* sa, const int32_t* sp,
    const int32_t* s1,
    int64_t n_u, const int32_t* sr, const int32_t* st,
    const int32_t* s2,
    const float* a, const float* p, const float* r,
    int32_t has_stage1, int32_t has_r, float* out) {
    return plan_values<float>(n_t, sa, sp, s1, n_u, sr, st, s2, a, p, r,
                              has_stage1, has_r, out);
}

}  // extern "C"
