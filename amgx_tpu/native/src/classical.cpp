// Host classical-AMG setup kernels: PMIS CF-splitting and distance-two
// (extended+i) interpolation.
//
// The reference runs these on the GPU with hash-table kernels
// (src/classical/selectors/pmis.cu, src/classical/interpolators/
// distance2.cu); on a remote TPU the setup-phase index math is
// latency-bound, so the host-setup path (amg_host_setup) runs them here
// as serial sweeps with stamp arrays — the same row-local structure the
// reference's per-CTA hash tables express, without the hardware hash.
//
// amgx_pmis is a bit-exact replica of the synchronous fixed point in
// amg/classical/selectors.py::pmis_split (same weights — exact halves
// plus the same integer hash — and the same two-phase round structure),
// so the CF-splitting is identical with or without the native library.
//
// amgx_d2_* implements the formula of amg/classical/interpolators.py::
// Distance2Interpolator (De Sterck et al. distance-two ext+i) with a
// handle-based build/fetch pair: the output size is data-dependent, so
// build computes and stashes the CSR, fetch copies it out and frees.
#include <algorithm>
#include <cmath>
#include <cstdint>
#include <vector>

namespace {

const int32_t FINE = 0, COARSE = 1, UNDECIDED = -1;

double hash01(uint32_t i) {
    uint32_t h = i * 2654435761u;
    h = (h ^ (h >> 16)) * 0x45D9F3Bu;
    h = h ^ (h >> 16);
    return static_cast<double>(h & 0xFFFFFu) / 1048576.0;
}

}  // namespace

extern "C" {

// PMIS fixed point. `init` may be null (all points start UNDECIDED) or
// hold {-1,0,1} seeds (HMIS). Writes cf[n] in {0,1}. Returns 0 on
// success. The two directions of a strength edge do different work, as
// in hypre's par_coarsen.c, which pmis.cu follows: row i's strong
// entries are what i DEPENDS on, column i's are what i INFLUENCES.
//   weight   w_i = |S^T_i| (the points that depend on i) + hash
//   start    a point that nothing depends on is FINE; the caller's
//            `init` makes FINE the points that depend on nothing (they
//            keep an empty row of P and are left to the smoother: the
//            reference's STRONG_FINE)
//   C        an undecided local maximum of w over its undecided
//            neighbours in S | S^T
//   F        an undecided point that DEPENDS on a C point: only then
//            has it a C point to interpolate from (on a symmetric mask
//            the direction does not matter; on a variable-coefficient
//            operator a point that merely influences a C point was
//            left without any, PR 47)
int amgx_pmis(
    int32_t n, const int32_t* ro, const int32_t* ci,
    const uint8_t* strong, const int32_t* init, int32_t max_iters,
    int32_t* cf) {
    // symmetrized adjacency S | S^T with duplicates kept (harmless for
    // a max); strong edges only, cols within [0, n): strength masks can
    // mark edges to halo/rectangular columns (same guard as rs.cpp)
    std::vector<int64_t> off(static_cast<size_t>(n) + 2, 0);
    std::vector<int32_t> indeg(static_cast<size_t>(n), 0);
    for (int32_t i = 0; i < n; ++i)
        for (int32_t e = ro[i]; e < ro[i + 1]; ++e) {
            const int32_t j = ci[e];
            if (strong[e] && j >= 0 && j < n) {
                ++off[static_cast<size_t>(i) + 2];
                ++off[static_cast<size_t>(j) + 2];
                ++indeg[static_cast<size_t>(j)];
            }
        }
    for (size_t i = 2; i < off.size(); ++i) off[i] += off[i - 1];
    std::vector<int32_t> adj(static_cast<size_t>(off[off.size() - 1]));
    for (int32_t i = 0; i < n; ++i)
        for (int32_t e = ro[i]; e < ro[i + 1]; ++e) {
            const int32_t j = ci[e];
            if (strong[e] && j >= 0 && j < n) {
                adj[static_cast<size_t>(off[static_cast<size_t>(i) + 1]++)] = j;
                adj[static_cast<size_t>(off[static_cast<size_t>(j) + 1]++)] = i;
            }
        }

    std::vector<double> w(static_cast<size_t>(n));
    std::vector<int32_t> state(static_cast<size_t>(n));
    for (int32_t i = 0; i < n; ++i) {
        w[static_cast<size_t>(i)] =
            static_cast<double>(indeg[static_cast<size_t>(i)]) +
            hash01(static_cast<uint32_t>(i));
        int32_t s = init ? init[i] : UNDECIDED;
        if (s == UNDECIDED && indeg[static_cast<size_t>(i)] == 0)
            s = FINE;
        state[static_cast<size_t>(i)] = s;
    }

    std::vector<uint8_t> new_c(static_cast<size_t>(n));
    for (int32_t it = 0; it < max_iters; ++it) {
        bool any_und = false;
        // phase 1: undecided local maxima over undecided strong
        // neighbours become COARSE (synchronous: decided against the
        // round-entry state)
        for (int32_t i = 0; i < n; ++i) {
            new_c[static_cast<size_t>(i)] = 0;
            if (state[static_cast<size_t>(i)] != UNDECIDED) continue;
            any_und = true;
            double nbr_max = -1.0;  // weights are >= 0; -1 == -inf here
            for (int64_t t = off[static_cast<size_t>(i)];
                 t < off[static_cast<size_t>(i) + 1]; ++t) {
                const int32_t j = adj[static_cast<size_t>(t)];
                if (state[static_cast<size_t>(j)] == UNDECIDED &&
                    w[static_cast<size_t>(j)] > nbr_max)
                    nbr_max = w[static_cast<size_t>(j)];
            }
            if (w[static_cast<size_t>(i)] > nbr_max)
                new_c[static_cast<size_t>(i)] = 1;
        }
        if (!any_und) break;
        for (int32_t i = 0; i < n; ++i)
            if (new_c[static_cast<size_t>(i)])
                state[static_cast<size_t>(i)] = COARSE;
        // phase 2: undecided points that depend on (any, including
        // new) COARSE points become FINE
        for (int32_t i = 0; i < n; ++i) {
            if (state[static_cast<size_t>(i)] != UNDECIDED) continue;
            for (int32_t e = ro[i]; e < ro[i + 1]; ++e) {
                const int32_t j = ci[e];
                if (strong[e] && j >= 0 && j < n &&
                    state[static_cast<size_t>(j)] == COARSE) {
                    state[static_cast<size_t>(i)] = FINE;
                    break;
                }
            }
        }
    }
    for (int32_t i = 0; i < n; ++i)
        cf[i] = state[static_cast<size_t>(i)] == COARSE ? COARSE : FINE;
    return 0;
}

// AHAT strength-of-connection mask (strength.py _strong_mask_host
// semantics; src/classical/strength/strength_base.cu analog):
//   strong_ij = offdiag & (-a_ij * sgn_i >= theta * rowmax_i) & (> 0)
// with max_row_sum weakening (rows with |rowsum| > mrs*|diag| lose all
// connections). Diagonal = FIRST in-row occurrence (padded-duplicate
// CSR convention). Writes strong[nnz] (uint8); returns the rows the
// row-sum rule weakened.
int64_t amgx_strength_ahat(
    int32_t n, const int32_t* ro, const int32_t* ci, const double* vals,
    double theta, double max_row_sum, uint8_t* strong) {
    int64_t weakened = 0;
    for (int32_t i = 0; i < n; ++i) {
        double diag = 0.0;
        bool have_diag = false;
        double rowsum = 0.0;
        for (int32_t e = ro[i]; e < ro[i + 1]; ++e) {
            rowsum += vals[e];
            if (!have_diag && ci[e] == i) { diag = vals[e]; have_diag = true; }
        }
        const double sgn = diag < 0.0 ? -1.0 : 1.0;
        double rowmax = 0.0;
        for (int32_t e = ro[i]; e < ro[i + 1]; ++e) {
            if (ci[e] == i) continue;
            const double c = -vals[e] * sgn;
            if (c > rowmax) rowmax = c;
        }
        const bool weak_row = max_row_sum < 1.0 &&
            std::abs(rowsum) > max_row_sum * std::abs(diag);
        weakened += weak_row;
        for (int32_t e = ro[i]; e < ro[i + 1]; ++e) {
            if (ci[e] == i || weak_row) { strong[e] = 0; continue; }
            const double c = -vals[e] * sgn;
            strong[e] = (c > 0.0 && c >= theta * rowmax) ? 1 : 0;
        }
    }
    return weakened;
}

// L1-strengthened Jacobi diagonal (jacobi_l1_solver.cu semantics;
// relaxation.py l1_strengthened_diag): d_i + sign(d_i) * sum|offdiag|,
// sign(0) = 0 so zero diagonals stay inert.
void amgx_l1_diag(
    int32_t n, const int32_t* ro, const int32_t* ci, const double* vals,
    double* out) {
    for (int32_t i = 0; i < n; ++i) {
        double diag = 0.0;
        bool have_diag = false;
        double l1 = 0.0;
        for (int32_t e = ro[i]; e < ro[i + 1]; ++e) {
            if (ci[e] == i) {
                if (!have_diag) { diag = vals[e]; have_diag = true; }
            } else {
                l1 += std::abs(vals[e]);
            }
        }
        const double s = diag > 0.0 ? 1.0 : (diag < 0.0 ? -1.0 : 0.0);
        out[i] = diag + s * l1;
    }
}

struct D2Result {
    std::vector<int64_t> ptr;
    std::vector<int32_t> col;
    std::vector<double> val;
};

// Distance-two ext+i interpolation. Inputs: scalar CSR (diagonal stored
// in-line), per-entry strength mask, cf map in {0,1}. Truncation
// (trunc_factor <= 1.0 and/or max_elements > 0; truncate.cu semantics —
// keep the max_elements largest |w| per row, drop entries below
// trunc_factor * rowmax, rescale survivors to preserve the row sum) is
// fused into the per-row emit so the untruncated P never materializes;
// *truncated_rows gets the rows that lost an entry to it.
// Returns P's nnz and a handle for amgx_d2_fetch; -1 on failure.
long long amgx_d2_build(
    int32_t n, const int32_t* ro, const int32_t* ci, const double* vals,
    const uint8_t* strong, const int32_t* cf, double trunc_factor,
    int32_t max_elements, int64_t* truncated_rows, void** out_handle) {
    *out_handle = nullptr;
    int64_t lost = 0;
    std::vector<double> diag(static_cast<size_t>(n), 0.0);
    std::vector<double> sgn(static_cast<size_t>(n), 1.0);
    std::vector<int32_t> cidx(static_cast<size_t>(n));
    int32_t nc = 0;
    for (int32_t i = 0; i < n; ++i) {
        for (int32_t e = ro[i]; e < ro[i + 1]; ++e)
            if (ci[e] == i) {  // FIRST occurrence wins (padded-duplicate
                diag[static_cast<size_t>(i)] = vals[e];  // CSR stores the
                break;  // coalesced sum first, trailing duplicates zero)
            }
        sgn[static_cast<size_t>(i)] =
            diag[static_cast<size_t>(i)] < 0.0 ? -1.0 : 1.0;
        cidx[static_cast<size_t>(i)] = nc;
        if (cf[i] == COARSE) ++nc;
    }

    auto* res = new D2Result();
    res->ptr.assign(static_cast<size_t>(n) + 1, 0);
    // stamp[l] == current row marks l in C-hat_i; acc holds the row's
    // coalesced interpolatory weights (pre -1/D scaling)
    std::vector<int32_t> stamp(static_cast<size_t>(n), -1);
    std::vector<int32_t> tstamp(static_cast<size_t>(n), -1);
    std::vector<double> acc(static_cast<size_t>(n), 0.0);
    std::vector<int32_t> touched;
    touched.reserve(64);
    std::vector<double> row_w;             // fused-truncation scratch
    std::vector<uint8_t> row_keep;
    std::vector<size_t> row_rank;

    // Pre-filtered per-row sublists, built once in O(nnz): the two-hop
    // loops below re-scan each strong-F neighbour's full row up to
    // three times per fine row; on D2 operators most entries fail the
    // filter every time. strongC = entries with strong && C (feeds the
    // C-hat stamping); neg = in-graph off-diagonal entries with
    // vals*sgn(k) < 0 (feeds the distribution sums). Entry order is
    // preserved, so the float accumulation order — and the emitted P —
    // is bit-identical to the unfiltered sweeps.
    std::vector<int64_t> sc_off(static_cast<size_t>(n) + 1, 0);
    std::vector<int64_t> ng_off(static_cast<size_t>(n) + 1, 0);
    for (int32_t k = 0; k < n; ++k) {
        int64_t csc = 0, cng = 0;
        const double sk = sgn[static_cast<size_t>(k)];
        for (int32_t f = ro[k]; f < ro[k + 1]; ++f) {
            const int32_t l = ci[f];
            if (l < 0 || l >= n) continue;
            if (strong[f] && cf[l] == COARSE) ++csc;
            if (l != k && vals[f] * sk < 0.0) ++cng;
        }
        sc_off[static_cast<size_t>(k) + 1] =
            sc_off[static_cast<size_t>(k)] + csc;
        ng_off[static_cast<size_t>(k) + 1] =
            ng_off[static_cast<size_t>(k)] + cng;
    }
    std::vector<int32_t> sc_col(static_cast<size_t>(sc_off[n]));
    std::vector<int32_t> ng_col(static_cast<size_t>(ng_off[n]));
    std::vector<double> ng_val(static_cast<size_t>(ng_off[n]));
    {
        std::vector<int64_t> ps = sc_off, pn = ng_off;
        for (int32_t k = 0; k < n; ++k) {
            const double sk = sgn[static_cast<size_t>(k)];
            for (int32_t f = ro[k]; f < ro[k + 1]; ++f) {
                const int32_t l = ci[f];
                if (l < 0 || l >= n) continue;
                if (strong[f] && cf[l] == COARSE)
                    sc_col[static_cast<size_t>(
                        ps[static_cast<size_t>(k)]++)] = l;
                if (l != k && vals[f] * sk < 0.0) {
                    const int64_t t = pn[static_cast<size_t>(k)]++;
                    ng_col[static_cast<size_t>(t)] = l;
                    ng_val[static_cast<size_t>(t)] = vals[f];
                }
            }
        }
    }

    for (int32_t i = 0; i < n; ++i) {
        res->ptr[static_cast<size_t>(i)] =
            static_cast<int64_t>(res->col.size());
        if (cf[i] == COARSE) {  // injection row
            res->col.push_back(cidx[static_cast<size_t>(i)]);
            res->val.push_back(1.0);
            continue;
        }
        // C-hat_i: strong C neighbours + strong-C neighbours of strong-F
        // neighbours (all members are C points)
        for (int64_t t = sc_off[static_cast<size_t>(i)];
             t < sc_off[static_cast<size_t>(i) + 1]; ++t)
            stamp[static_cast<size_t>(sc_col[static_cast<size_t>(t)])] = i;
        for (int32_t e = ro[i]; e < ro[i + 1]; ++e) {
            const int32_t k = ci[e];
            if (k < 0 || k >= n) continue;
            if (!(strong[e] && cf[k] == FINE && k != i)) continue;
            for (int64_t t = sc_off[static_cast<size_t>(k)];
                 t < sc_off[static_cast<size_t>(k) + 1]; ++t)
                stamp[static_cast<size_t>(
                    sc_col[static_cast<size_t>(t)])] = i;
        }
        touched.clear();
        double D = diag[static_cast<size_t>(i)];
        auto acc_add = [&](int32_t j, double v) {
            if (tstamp[static_cast<size_t>(j)] != i) {
                tstamp[static_cast<size_t>(j)] = i;
                acc[static_cast<size_t>(j)] = 0.0;
                touched.push_back(j);
            }
            acc[static_cast<size_t>(j)] += v;
        };
        // direct entries + weak lumping
        for (int32_t e = ro[i]; e < ro[i + 1]; ++e) {
            const int32_t j = ci[e];
            if (j == i) continue;
            if (j < 0 || j >= n) {  // out-of-graph column: weak-lump
                D += vals[e];
                continue;
            }
            const bool in_chat = stamp[static_cast<size_t>(j)] == i;
            const bool strong_f = strong[e] && cf[j] == FINE;
            if (in_chat && cf[j] == COARSE) acc_add(j, vals[e]);
            if (!in_chat && !strong_f) D += vals[e];
        }
        // two-hop terms through strong F neighbours (the negative
        // in-graph sublist of row k is exactly the entry set the
        // original full-row scans kept — same entries, same order)
        for (int32_t e = ro[i]; e < ro[i + 1]; ++e) {
            const int32_t k = ci[e];
            if (k < 0 || k >= n) continue;
            if (!(strong[e] && cf[k] == FINE && k != i)) continue;
            const double aik = vals[e];
            const int64_t f0 = ng_off[static_cast<size_t>(k)];
            const int64_t f1 = ng_off[static_cast<size_t>(k) + 1];
            double d = 0.0;
            for (int64_t f = f0; f < f1; ++f) {
                const int32_t l = ng_col[static_cast<size_t>(f)];
                if (stamp[static_cast<size_t>(l)] == i || l == i)
                    d += ng_val[static_cast<size_t>(f)];
            }
            if (d == 0.0) {  // k distributes nowhere: lump a_ik
                D += aik;
                continue;
            }
            for (int64_t f = f0; f < f1; ++f) {
                const int32_t l = ng_col[static_cast<size_t>(f)];
                const double v = ng_val[static_cast<size_t>(f)];
                if (l == i)
                    D += aik * v / d;  // "+i" feedback
                else if (stamp[static_cast<size_t>(l)] == i)
                    acc_add(l, aik * v / d);
            }
        }
        std::sort(touched.begin(), touched.end());
        const double dsafe = D == 0.0 ? 1.0 : D;
        const bool truncate = trunc_factor <= 1.0 || max_elements > 0;
        if (!truncate) {
            for (const int32_t j : touched) {
                res->col.push_back(cidx[static_cast<size_t>(j)]);
                res->val.push_back(-acc[static_cast<size_t>(j)] / dsafe);
            }
            continue;
        }
        // fused truncation (matches _truncate_host: stable top-k by
        // descending |w| with earlier-column tie wins, trunc_factor
        // drop, row-sum-preserving rescale; sums in column order)
        row_w.clear();
        double rowsum = 0.0, wmax = 0.0;
        for (const int32_t j : touched) {
            const double w = -acc[static_cast<size_t>(j)] / dsafe;
            row_w.push_back(w);
            rowsum += w;
            if (std::abs(w) > wmax) wmax = std::abs(w);
        }
        const size_t m = row_w.size();
        row_keep.assign(m, 1);
        if (trunc_factor <= 1.0)
            for (size_t t = 0; t < m; ++t)
                if (std::abs(row_w[t]) < trunc_factor * wmax)
                    row_keep[t] = 0;
        if (max_elements > 0 && m > static_cast<size_t>(max_elements)) {
            row_rank.resize(m);
            for (size_t t = 0; t < m; ++t) row_rank[t] = t;
            std::stable_sort(row_rank.begin(), row_rank.end(),
                             [&](size_t a, size_t b) {
                                 return std::abs(row_w[a]) >
                                        std::abs(row_w[b]);
                             });
            for (size_t r = static_cast<size_t>(max_elements); r < m; ++r)
                row_keep[row_rank[r]] = 0;
        }
        double keptsum = 0.0;
        size_t kept = 0;
        for (size_t t = 0; t < m; ++t)
            if (row_keep[t]) { keptsum += row_w[t]; ++kept; }
        lost += kept < m;
        const double scale = keptsum == 0.0 ? 1.0 : rowsum / keptsum;
        for (size_t t = 0; t < m; ++t) {
            if (!row_keep[t]) continue;
            res->col.push_back(cidx[static_cast<size_t>(touched[t])]);
            res->val.push_back(row_w[t] * scale);
        }
    }
    res->ptr[static_cast<size_t>(n)] = static_cast<int64_t>(res->col.size());
    *truncated_rows = lost;
    *out_handle = res;
    return static_cast<long long>(res->col.size());
}

void amgx_d2_fetch(void* handle, int64_t* ptr, int32_t* col, double* val) {
    auto* res = static_cast<D2Result*>(handle);
    std::copy(res->ptr.begin(), res->ptr.end(), ptr);
    std::copy(res->col.begin(), res->col.end(), col);
    std::copy(res->val.begin(), res->val.end(), val);
    delete res;
}

void amgx_d2_free(void* handle) { delete static_cast<D2Result*>(handle); }

}  // extern "C"

// Stueben's multipass interpolation (amg/classical/interpolators.py
// MultipassInterpolator has the formula; multipass.cu analog), rows
// whole: an F point's pass is its distance to the C set over strong
// negative couplings; pass 1 interpolates from its C points, pass p
// through the rows of the points of earlier passes it depends on,
//   w_i = -(alpha_i / ~a_ii) sum_{j in J_i} a_ij P_j,
//   alpha_i = sum_{k != i, a_ik < 0} a_ik / sum_{j in J_i} a_ij,
// ~a_ii the diagonal plus the positive couplings. The numpy form over
// the native SpGEMM took 12 s more of a 128^3 set-up than the device
// programs it replaced (PR 47); this is one sweep a pass. Returns P's
// nnz and a handle for amgx_d2_fetch / amgx_d2_free; -1 on failure.
extern "C" long long amgx_multipass_build(
    int32_t n, const int32_t* ro, const int32_t* ci, const double* vals,
    const uint8_t* strong, const int32_t* cf, void** out_handle) {
    *out_handle = nullptr;
    const size_t N = static_cast<size_t>(n);
    const int32_t FAR = 1 << 30;
    std::vector<int32_t> cidx(N, -1), pass(N, FAR);
    int32_t nc = 0;
    for (int32_t i = 0; i < n; ++i)
        if (cf[i] == COARSE) { cidx[i] = nc++; pass[i] = 0; }
    auto through = [&](int32_t i, int32_t e) {   // a strong negative coupling
        return strong[e] && ci[e] != i && vals[e] < 0.0 &&
               ci[e] >= 0 && ci[e] < n;
    };
    int32_t max_pass = 0;
    for (int sweep = 0; sweep < 64; ++sweep) {
        bool moved = false;
        for (int32_t i = 0; i < n; ++i) {
            if (cf[i] == COARSE) continue;
            int32_t best = pass[i];
            for (int32_t e = ro[i]; e < ro[i + 1]; ++e)
                if (through(i, e) && pass[ci[e]] + 1 < best)
                    best = pass[ci[e]] + 1;
            if (best < pass[i]) { pass[i] = best; moved = true; }
        }
        if (!moved) break;
    }
    std::vector<std::vector<int32_t>> by_pass;
    for (int32_t i = 0; i < n; ++i) {
        if (pass[i] == 0 || pass[i] >= FAR) continue;
        if (pass[i] > max_pass) { max_pass = pass[i]; by_pass.resize(max_pass); }
        by_pass[static_cast<size_t>(pass[i]) - 1].push_back(i);
    }
    // rows are made pass by pass, out of row order: a pool, then CSR
    std::vector<int64_t> start(N, 0);
    std::vector<int32_t> len(N, 0), pool_col;
    std::vector<double> pool_val, acc(static_cast<size_t>(nc), 0.0);
    std::vector<int32_t> touched;
    for (int32_t i = 0; i < n; ++i)
        if (cf[i] == COARSE) {
            start[i] = static_cast<int64_t>(pool_col.size());
            len[i] = 1;
            pool_col.push_back(cidx[i]);
            pool_val.push_back(1.0);
        }
    for (int32_t p = 1; p <= max_pass; ++p)
        for (const int32_t i : by_pass[static_cast<size_t>(p) - 1]) {
            double dmod = 0.0, sum_neg = 0.0, denom = 0.0;
            touched.clear();
            for (int32_t e = ro[i]; e < ro[i + 1]; ++e) {
                const int32_t j = ci[e];
                if (j != i && vals[e] < 0.0) sum_neg += vals[e];
                else dmod += vals[e];
                if (!through(i, e) || pass[j] >= p) continue;
                denom += vals[e];
                for (int64_t t = start[j]; t < start[j] + len[j]; ++t) {
                    const int32_t c = pool_col[static_cast<size_t>(t)];
                    if (acc[c] == 0.0) touched.push_back(c);
                    acc[c] += vals[e] * pool_val[static_cast<size_t>(t)];
                }
            }
            const double scale = denom == 0.0 ? 0.0
                : -(sum_neg / denom) / (dmod == 0.0 ? 1.0 : dmod);
            std::sort(touched.begin(), touched.end());
            start[i] = static_cast<int64_t>(pool_col.size());
            for (const int32_t c : touched) {
                if (acc[c] * scale != 0.0) {
                    pool_col.push_back(c);
                    pool_val.push_back(acc[c] * scale);
                }
                acc[c] = 0.0;
            }
            len[i] = static_cast<int32_t>(
                static_cast<int64_t>(pool_col.size()) - start[i]);
        }
    auto* res = new D2Result();
    res->ptr.assign(N + 1, 0);
    for (int32_t i = 0; i < n; ++i) res->ptr[i + 1] = res->ptr[i] + len[i];
    res->col.resize(static_cast<size_t>(res->ptr[N]));
    res->val.resize(static_cast<size_t>(res->ptr[N]));
    for (int32_t i = 0; i < n; ++i)
        for (int32_t t = 0; t < len[i]; ++t) {
            res->col[static_cast<size_t>(res->ptr[i] + t)] =
                pool_col[static_cast<size_t>(start[i] + t)];
            res->val[static_cast<size_t>(res->ptr[i] + t)] =
                pool_val[static_cast<size_t>(start[i] + t)];
        }
    *out_handle = res;
    return static_cast<long long>(res->col.size());
}

// The pattern of S@S without its diagonal: row i holds every j != i
// that i depends on in exactly two steps (the aggressive selector's
// graph). A stamp a row, no values, columns in the order met: the
// SpGEMM with float64 path counts and sorted rows, and the COO views
// round it, were 7 of the 10 s a 128^3 level's split took on the host
// (PR 47). Returns the nnz and a handle for amgx_d2_fetch (no values).
extern "C" long long amgx_two_step_pattern(
    int32_t n, const int32_t* ro, const int32_t* ci, const uint8_t* strong,
    void** out_handle) {
    auto* res = new D2Result();
    res->ptr.assign(static_cast<size_t>(n) + 1, 0);
    std::vector<int32_t> stamp(static_cast<size_t>(n), -1);
    for (int32_t i = 0; i < n; ++i) {
        stamp[i] = i;                        // never the diagonal
        for (int32_t e = ro[i]; e < ro[i + 1]; ++e) {
            const int32_t k = ci[e];
            if (!strong[e] || k < 0 || k >= n) continue;
            for (int32_t f = ro[k]; f < ro[k + 1]; ++f) {
                const int32_t j = ci[f];
                if (!strong[f] || j < 0 || j >= n || stamp[j] == i) continue;
                stamp[j] = i;
                res->col.push_back(j);
            }
        }
        res->ptr[static_cast<size_t>(i) + 1] =
            static_cast<int64_t>(res->col.size());
    }
    *out_handle = res;
    return static_cast<long long>(res->col.size());
}
