// Native hot path of bin-bound construction.
//
// The reference computes bin bounds in C++ (GreedyFindBin, src/io/bin.cpp:
// 74-150); the Python re-expression in lightgbm_tpu/core/binning.py walks
// every distinct sample value in an interpreter loop (~0.4s per feature at
// the default 200k-row binning sample), which dominated dataset
// construction on the single-core host.  This file implements the SAME
// algorithm as the Python version (which is the spec; bounds must match it
// bit-for-bit) as a small ctypes-loaded shared object.
//
// Built on demand by lightgbm_tpu/core/native.py with the system g++.

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <limits>

namespace {

inline double next_after_up(double a) {
    return std::nextafter(a, std::numeric_limits<double>::infinity());
}

inline bool double_equal_ordered(double a, double b) {
    return b <= next_after_up(a);
}

// push a candidate bound if it is distinct from the previous one
inline void push_bound(double val, double* out, int64_t* n_out) {
    if (*n_out == 0 || !double_equal_ordered(out[*n_out - 1], val)) {
        out[(*n_out)++] = val;
    }
}

}  // namespace

extern "C" {

// distinct[n], counts[n] -> bounds written to out (caller allocates
// max_bin + 1 doubles); returns the number of bounds (always >= 1, the
// last is +inf).  Mirrors lightgbm_tpu.core.binning.greedy_find_bin.
int64_t lgbmtpu_greedy_find_bin(const double* distinct,
                                const int64_t* counts, int64_t n,
                                int64_t max_bin, int64_t total_cnt,
                                int64_t min_data_in_bin, double* out) {
    int64_t n_out = 0;
    if (n <= max_bin) {
        int64_t cur_cnt = 0;
        for (int64_t i = 0; i + 1 < n; ++i) {
            cur_cnt += counts[i];
            if (cur_cnt >= min_data_in_bin) {
                double val = next_after_up((distinct[i] + distinct[i + 1])
                                           / 2.0);
                int64_t before = n_out;
                push_bound(val, out, &n_out);
                if (n_out > before) cur_cnt = 0;
            }
        }
        out[n_out++] = std::numeric_limits<double>::infinity();
        return n_out;
    }

    if (min_data_in_bin > 0) {
        int64_t cap = total_cnt / min_data_in_bin;
        if (cap < max_bin) max_bin = cap;
        if (max_bin < 1) max_bin = 1;
    }
    double mean_bin_size = double(total_cnt) / double(max_bin);
    int64_t n_big = 0, big_cnt = 0;
    for (int64_t i = 0; i < n; ++i) {
        if (double(counts[i]) >= mean_bin_size) {
            ++n_big;
            big_cnt += counts[i];
        }
    }
    int64_t rest_bin_cnt = max_bin - n_big;
    int64_t rest_sample_cnt = total_cnt - big_cnt;
    mean_bin_size = double(rest_sample_cnt)
        / double(rest_bin_cnt > 1 ? rest_bin_cnt : 1);

    // upper/lower bounds of the greedily-chosen value runs
    double* uppers = new double[max_bin];
    double* lowers = new double[max_bin + 1];
    int64_t bin_cnt = 0;
    lowers[0] = distinct[0];
    int64_t cur_cnt = 0;
    // the is_big test uses the ORIGINAL mean (the mask is computed once
    // up front in the Python spec), not the re-weighted running mean
    const double mean0 = double(total_cnt) / double(max_bin);
    for (int64_t i = 0; i + 1 < n; ++i) {
        const bool is_big_i = double(counts[i]) >= mean0;
        const bool is_big_next = double(counts[i + 1]) >= mean0;
        if (!is_big_i) rest_sample_cnt -= counts[i];
        cur_cnt += counts[i];
        if (is_big_i || double(cur_cnt) >= mean_bin_size ||
            (is_big_next && double(cur_cnt) >=
             (mean_bin_size * 0.5 > 1.0 ? mean_bin_size * 0.5 : 1.0))) {
            uppers[bin_cnt] = distinct[i];
            ++bin_cnt;
            lowers[bin_cnt] = distinct[i + 1];
            if (bin_cnt >= max_bin - 1) break;
            cur_cnt = 0;
            if (!is_big_i) {
                --rest_bin_cnt;
                mean_bin_size = double(rest_sample_cnt)
                    / double(rest_bin_cnt > 1 ? rest_bin_cnt : 1);
            }
        }
    }
    ++bin_cnt;
    for (int64_t i = 0; i + 1 < bin_cnt; ++i) {
        push_bound(next_after_up((uppers[i] + lowers[i + 1]) / 2.0),
                   out, &n_out);
    }
    out[n_out++] = std::numeric_limits<double>::infinity();
    delete[] uppers;
    delete[] lowers;
    return n_out;
}

// values[n] -> bins[n] for NUMERICAL mappers: first bound index with
// value <= bound, searched over bounds[0..n_search-1) (the vectorized
// np.searchsorted in BinMapper.value_to_bin); NaNs handled by the caller.
void lgbmtpu_values_to_bins(const double* values, int64_t n,
                            const double* bounds, int64_t n_search,
                            int32_t* out) {
    for (int64_t i = 0; i < n; ++i) {
        double v = values[i];
        int64_t lo = 0, hi = n_search;     // search [lo, hi)
        while (lo < hi) {
            int64_t mid = (lo + hi) >> 1;
            if (bounds[mid] < v) lo = mid + 1; else hi = mid;
        }
        out[i] = int32_t(lo);
    }
}

}  // extern "C"

namespace {

// cache-blocked matrix quantization; NaN routed per missing_type.
// T = float or double input; OutT = uint8_t or uint16_t bins.
//
// bin = #{b : ub[b] < v} == searchsorted(ub, v, side=left).  For the
// common narrow-bin case the count runs as a BRANCHLESS linear scan
// the compiler vectorizes (a binary search mispredicts ~every level on
// shuffled data — measured 42 ns/value; the SIMD count is ~6 ns); wide
// bound sets (u16 datasets) keep a branchless binary search.
constexpr int64_t kQChunk = 2048;
constexpr int64_t kLinearMax = 128;
// Rows of a chunk: a chunk's input rows are walked once a column, so
// they have to stay in cache between columns.  2048 rows of 28 floats
// are 229 KB; 2048 rows of 2000 floats would be 16 MB and every strided
// read a miss (72 s for 1.1M x 2000, builder's chip run, PR 30), so wide
// tables take fewer rows a chunk, the same bytes.
constexpr int64_t kQChunkBytes = 256 * 1024;

inline int64_t chunk_rows(int64_t f_total, int64_t value_bytes) {
    const int64_t fit = kQChunkBytes / std::max<int64_t>(
        1, f_total * value_bytes);
    return std::max<int64_t>(32, std::min(kQChunk, fit));
}

template <typename T, typename OutT>
void quantize_rows(const T* data, int64_t n, int64_t f_total,
                   const int64_t* feat_idx, int64_t n_used,
                   const double* bounds_flat, const int64_t* bounds_off,
                   const int32_t* missing_type, const int32_t* num_bin,
                   OutT* out) {
    double buf[kQChunk];
    const int64_t rows = chunk_rows(f_total, sizeof(T));
    for (int64_t c0 = 0; c0 < n; c0 += rows) {
        int64_t c = std::min(rows, n - c0);
        for (int64_t j = 0; j < n_used; ++j) {
            const T* col = data + c0 * f_total + feat_idx[j];
            const double* ub = bounds_flat + bounds_off[j];
            const int64_t nb = bounds_off[j + 1] - bounds_off[j];
            const bool nan_last = missing_type[j] == 2;
            const OutT nan_bin = OutT(num_bin[j] - 1);
            OutT* o = out + c0 * n_used + j;
            // strided gather to a contiguous scratch (NaN -> 0.0, the
            // value_to_bin substitution; core/binning.py:382)
            for (int64_t i = 0; i < c; ++i) {
                double v = double(col[i * f_total]);
                buf[i] = std::isnan(v) ? 0.0 : v;
            }
            if (nb <= kLinearMax) {
                for (int64_t i = 0; i < c; ++i) {
                    const double v = buf[i];
                    int64_t cnt = 0;
                    for (int64_t b = 0; b < nb; ++b) {
                        cnt += ub[b] < v;          // vectorized count
                    }
                    o[i * n_used] = OutT(cnt);
                }
            } else {
                for (int64_t i = 0; i < c; ++i) {
                    const double v = buf[i];
                    const double* base = ub;
                    int64_t len = nb;
                    while (len > 1) {              // branchless lower_bound
                        int64_t half = len >> 1;
                        base += (base[half - 1] < v) ? half : 0;
                        len -= half;
                    }
                    o[i * n_used] =
                        OutT((base - ub) + (nb > 0 && base[0] < v ? 1 : 0));
                }
            }
            if (nan_last) {
                for (int64_t i = 0; i < c; ++i) {
                    if (std::isnan(double(col[i * f_total]))) {
                        o[i * n_used] = nan_bin;
                    }
                }
            }
        }
    }
}

// f32 fast path: thresholds t[b] are the smallest floats whose f64
// value exceeds the column's f64 bound, so the f64 rule
// "count ub[b] < (double)v" is EXACTLY "count v >= t[b]" in pure f32
// (the caller precomputes t; exactness argued in core/native.py).
// One f32 SIMD lane carries 2x the f64 width and skips the
// double-conversion gather.
void quantize_rows_f32_thr(const float* data, int64_t n, int64_t f_total,
                           const int64_t* feat_idx, int64_t n_used,
                           const float* thr_flat,
                           const int64_t* bounds_off,
                           const int32_t* missing_type,
                           const int32_t* num_bin, uint8_t* out) {
    float buf[kQChunk];
    const int64_t rows = chunk_rows(f_total, sizeof(float));
    for (int64_t c0 = 0; c0 < n; c0 += rows) {
        int64_t c = std::min(rows, n - c0);
        for (int64_t j = 0; j < n_used; ++j) {
            const float* col = data + c0 * f_total + feat_idx[j];
            const float* thr = thr_flat + bounds_off[j];
            const int64_t nb = bounds_off[j + 1] - bounds_off[j];
            const bool nan_last = missing_type[j] == 2;
            const uint8_t nan_bin = uint8_t(num_bin[j] - 1);
            uint8_t* o = out + c0 * n_used + j;
            for (int64_t i = 0; i < c; ++i) {
                float v = col[i * f_total];
                buf[i] = std::isnan(v) ? 0.0f : v;
            }
            for (int64_t i = 0; i < c; ++i) {
                const float v = buf[i];
                int32_t cnt = 0;
                for (int64_t b = 0; b < nb; ++b) {
                    cnt += v >= thr[b];
                }
                o[i * n_used] = uint8_t(cnt);
            }
            if (nan_last) {
                for (int64_t i = 0; i < c; ++i) {
                    if (std::isnan(col[i * f_total])) {
                        o[i * n_used] = nan_bin;
                    }
                }
            }
        }
    }
}

}  // namespace

extern "C" {

// f32-input, u8-output, narrow-bounds fast path (see
// quantize_rows_f32_thr above); thr_flat are the caller-precomputed
// exact f32 thresholds.
void lgbmtpu_quantize_rows_f32(const float* data, int64_t n,
                               int64_t f_total, const int64_t* feat_idx,
                               int64_t n_used, const float* thr_flat,
                               const int64_t* bounds_off,
                               const int32_t* missing_type,
                               const int32_t* num_bin, uint8_t* out) {
    quantize_rows_f32_thr(data, n, f_total, feat_idx, n_used, thr_flat,
                          bounds_off, missing_type, num_bin, out);
}

// Whole-matrix quantization (the ValueToBin application loop the
// reference runs in C++, src/io/dataset_loader.cpp push paths): one
// cache-friendly pass over the row-major [n, f_total] data instead of
// one strided column copy + searchsorted per feature.  ``bounds_off``
// has n_used + 1 entries delimiting each used column's TRUNCATED bound
// slice (ub[:max(n_search - 1, 0)]); ``is_f64``/``is_u16`` pick the
// input/output widths.
void lgbmtpu_quantize_rows(const void* data, int64_t is_f64, int64_t n,
                           int64_t f_total, const int64_t* feat_idx,
                           int64_t n_used, const double* bounds_flat,
                           const int64_t* bounds_off,
                           const int32_t* missing_type,
                           const int32_t* num_bin, int64_t is_u16,
                           void* out) {
    if (is_f64) {
        if (is_u16)
            quantize_rows((const double*)data, n, f_total, feat_idx,
                          n_used, bounds_flat, bounds_off, missing_type,
                          num_bin, (uint16_t*)out);
        else
            quantize_rows((const double*)data, n, f_total, feat_idx,
                          n_used, bounds_flat, bounds_off, missing_type,
                          num_bin, (uint8_t*)out);
    } else {
        if (is_u16)
            quantize_rows((const float*)data, n, f_total, feat_idx,
                          n_used, bounds_flat, bounds_off, missing_type,
                          num_bin, (uint16_t*)out);
        else
            quantize_rows((const float*)data, n, f_total, feat_idx,
                          n_used, bounds_flat, bounds_off, missing_type,
                          num_bin, (uint8_t*)out);
    }
}

}  // extern "C"
