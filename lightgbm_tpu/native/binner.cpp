// Native host-side data kernels: CSV/TSV parsing and bin transformation.
//
// Reference: src/io/parser.cpp (CSV/TSV/LibSVM parser with fast_double_parser) and
// src/io/bin.cpp BinMapper::ValueToBin / dense_bin.hpp Push. These are the host-side
// hot paths of dataset construction (the TPU owns everything after binning); a
// vectorised C++17 implementation with OpenMP keeps ingest off the Python interpreter.
//
// Exposed C ABI (ctypes):
//   lgbt_parse_csv     — parse a delimited text buffer into a dense double matrix
//   lgbt_value_to_bin  — upper_bounds binary-search transform, OpenMP over rows
//   lgbt_rows_cols     — count rows/cols of a delimited buffer (sizing pass)
#include <algorithm>
#include <atomic>
#include <cctype>
#include <cmath>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <limits>
#include <vector>

#if defined(_OPENMP)
#include <omp.h>
#endif

extern "C" {

// Fast strtod-lite: handles the common numeric forms in data files; falls back to
// strtod for exotic inputs.
static double parse_double(const char* p, const char* end, const char** out) {
  while (p < end && (*p == ' ' || *p == '\t')) ++p;
  if (p >= end) { *out = p; return std::numeric_limits<double>::quiet_NaN(); }
  bool neg = false;
  if (*p == '-') { neg = true; ++p; }
  else if (*p == '+') { ++p; }
  // nan / inf
  if (p < end && (*p == 'n' || *p == 'N')) {
    *out = p + 3 <= end ? p + 3 : end;
    return std::numeric_limits<double>::quiet_NaN();
  }
  if (p < end && (*p == 'i' || *p == 'I')) {
    *out = p + 3 <= end ? p + 3 : end;
    double v = std::numeric_limits<double>::infinity();
    return neg ? -v : v;
  }
  uint64_t mant = 0;
  int digits = 0, dp_offset = 0, consumed = 0;
  bool saw_dot = false;
  while (p < end) {
    char c = *p;
    if (c >= '0' && c <= '9') {
      if (digits < 18) { mant = mant * 10 + (c - '0'); ++digits; if (saw_dot) --dp_offset; }
      else if (!saw_dot) ++dp_offset;
      ++consumed;
      ++p;
    } else if (c == '.' && !saw_dot) {
      saw_dot = true; ++p;
    } else {
      break;
    }
  }
  if (consumed == 0) {  // empty / non-numeric field -> missing, not 0.0
    *out = p;
    return std::numeric_limits<double>::quiet_NaN();
  }
  double v = static_cast<double>(mant);
  int exp10 = dp_offset;
  if (p < end && (*p == 'e' || *p == 'E')) {
    ++p;
    bool eneg = false;
    if (p < end && (*p == '-')) { eneg = true; ++p; }
    else if (p < end && (*p == '+')) ++p;
    int e = 0;
    while (p < end && *p >= '0' && *p <= '9') { e = e * 10 + (*p - '0'); ++p; }
    exp10 += eneg ? -e : e;
  }
  if (exp10 != 0) v *= std::pow(10.0, exp10);
  *out = p;
  return neg ? -v : v;
}

// Count data rows and columns (first sizing pass).
void lgbt_rows_cols(const char* buf, int64_t len, char delim, int skip_header,
                    int64_t* out_rows, int64_t* out_cols) {
  int64_t rows = 0, cols = 0;
  const char* p = buf;
  const char* end = buf + len;
  bool first_line = true;
  while (p < end) {
    const char* nl = static_cast<const char*>(memchr(p, '\n', end - p));
    const char* line_end = nl ? nl : end;
    if (line_end > p && line_end[-1] == '\r') --line_end;  // CRLF
    if (line_end > p) {
      if (first_line && skip_header) {
        first_line = false;
      } else {
        if (cols == 0) {
          int64_t c = 1;
          for (const char* q = p; q < line_end; ++q)
            if (*q == delim) ++c;
          cols = c;
        }
        ++rows;
        first_line = false;
      }
    }
    if (!nl) break;
    p = nl + 1;
  }
  *out_rows = rows;
  *out_cols = cols;
}

// Parse a delimited buffer into out[rows*cols] (row-major). Rows are located in a
// serial newline scan, then parsed in parallel.
void lgbt_parse_csv(const char* buf, int64_t len, char delim, int skip_header,
                    int64_t rows, int64_t cols, double* out) {
  std::vector<const char*> line_starts;
  line_starts.reserve(rows + 1);
  const char* p = buf;
  const char* end = buf + len;
  bool first_line = true;
  while (p < end && static_cast<int64_t>(line_starts.size()) < rows) {
    const char* nl = static_cast<const char*>(memchr(p, '\n', end - p));
    const char* line_end = nl ? nl : end;
    if (line_end > p && line_end[-1] == '\r') --line_end;  // CRLF
    if (line_end > p) {
      if (first_line && skip_header) {
        first_line = false;
      } else {
        line_starts.push_back(p);
        first_line = false;
      }
    }
    if (!nl) break;
    p = nl + 1;
  }
  const int64_t n = static_cast<int64_t>(line_starts.size());
#if defined(_OPENMP)
#pragma omp parallel for schedule(static)
#endif
  for (int64_t r = 0; r < n; ++r) {
    const char* q = line_starts[r];
    const char* line_end = static_cast<const char*>(
        memchr(q, '\n', end - q));
    if (!line_end) line_end = end;
    double* row_out = out + r * cols;
    for (int64_t c = 0; c < cols; ++c) {
      if (q >= line_end) {
        row_out[c] = std::numeric_limits<double>::quiet_NaN();
        continue;
      }
      const char* next;
      row_out[c] = parse_double(q, line_end, &next);
      q = next;
      while (q < line_end && *q != delim) ++q;
      if (q < line_end) ++q;  // skip delimiter
    }
  }
}

// One value's bin by the upper-bound binary search (reference:
// BinMapper::ValueToBin, bin.h:613). missing_type: 0 none, 1 zero-as-missing,
// 2 nan.  NaN -> last bin under MissingType::NaN (2); otherwise NaN is binned
// as 0.0 — the zero window [-kZeroThreshold, kZeroThreshold] is a real bin of
// its own.
static inline int32_t value_bin(double v, const double* upper_bounds,
                                int32_t num_bounds, int32_t missing_type,
                                int32_t num_bins) {
  if (std::isnan(v)) {
    if (missing_type == 2) return num_bins - 1;
    v = 0.0;
  }
  // first index with upper_bounds[idx] >= v
  int32_t lo = 0, hi = num_bounds - 1;
  while (lo < hi) {
    int32_t mid = (lo + hi) / 2;
    if (upper_bounds[mid] < v) lo = mid + 1; else hi = mid;
  }
  return lo;
}

// values[n] -> bins[n], OpenMP over the values
void lgbt_value_to_bin(const double* values, int64_t n,
                       const double* upper_bounds, int32_t num_bounds,
                       int32_t missing_type, int32_t num_bins,
                       int32_t default_bin, uint16_t* out) {
#if defined(_OPENMP)
#pragma omp parallel for schedule(static)
#endif
  for (int64_t i = 0; i < n; ++i) {
    out[i] = static_cast<uint16_t>(value_bin(values[i], upper_bounds,
                                             num_bounds, missing_type,
                                             num_bins));
  }
}


}  // extern "C": a template has C++ linkage

// A whole row-major table at once: out[i, g] = bin of X[i, col_of_group[g]]
// by value_bin, rows in parallel.  The column-at-a-time path reads and
// writes every cache line of the table once a COLUMN (a stride of a row);
// here a row is read once and its bins written side by side.  Group g's
// upper bounds are bounds[bounds_off[g] .. bounds_off[g + 1]).
template <typename T>
static void bin_rows(const T* X, int64_t n, int64_t row_stride,
                     const int32_t* col_of_group, int32_t num_groups,
                     const double* bounds, const int64_t* bounds_off,
                     const int32_t* missing_type, const int32_t* num_bins,
                     uint8_t* out) {
#if defined(_OPENMP)
#pragma omp parallel for schedule(static)
#endif
  for (int64_t i = 0; i < n; ++i) {
    const T* row = X + i * row_stride;
    uint8_t* row_out = out + i * num_groups;
    for (int32_t g = 0; g < num_groups; ++g) {
      row_out[g] = static_cast<uint8_t>(value_bin(
          static_cast<double>(row[col_of_group[g]]), bounds + bounds_off[g],
          static_cast<int32_t>(bounds_off[g + 1] - bounds_off[g]),
          missing_type[g], num_bins[g]));
    }
  }
}

extern "C" {

void lgbt_bin_rows_f32(const float* X, int64_t n, int64_t row_stride,
                       const int32_t* col_of_group, int32_t num_groups,
                       const double* bounds, const int64_t* bounds_off,
                       const int32_t* missing_type, const int32_t* num_bins,
                       uint8_t* out) {
  bin_rows(X, n, row_stride, col_of_group, num_groups, bounds, bounds_off,
           missing_type, num_bins, out);
}

void lgbt_bin_rows_f64(const double* X, int64_t n, int64_t row_stride,
                       const int32_t* col_of_group, int32_t num_groups,
                       const double* bounds, const int64_t* bounds_off,
                       const int32_t* missing_type, const int32_t* num_bins,
                       uint8_t* out) {
  bin_rows(X, n, row_stride, col_of_group, num_groups, bounds, bounds_off,
           missing_type, num_bins, out);
}


// Single-row fast prediction: walk every tree of a packed model for one raw
// feature row (reference: include/LightGBM/c_api.h:1399
// LGBM_BoosterPredictForMatSingleRowFastInit/Fast + Tree::Predict, tree.h:135).
// All node arrays are the trees' internal-node arrays concatenated; tree t's
// nodes live at [tree_off[t], tree_off[t+1]) and its leaves at leaf_off[t].
// Child encoding follows the text-model convention: >=0 internal, <0 => leaf
// index ~child. decision_type bits: 1=categorical, 2=default_left,
// bits 2-3 missing type (0 none, 1 zero, 2 nan).
void lgbt_predict_row(const double* row,
                      const int32_t* tree_off, int32_t ntrees,
                      const int32_t* split_feature, const double* threshold,
                      const int32_t* threshold_bin,
                      const uint8_t* decision_type,
                      const int32_t* left, const int32_t* right,
                      const int32_t* leaf_off, const double* leaf_value,
                      const int32_t* cat_boundaries,
                      const uint32_t* cat_threshold,
                      int32_t num_class, double* out) {
  for (int32_t t = 0; t < ntrees; ++t) {
    const int32_t nb = tree_off[t];
    const int32_t nnodes = tree_off[t + 1] - nb;
    double leaf;
    if (nnodes <= 0) {
      leaf = leaf_value[leaf_off[t]];
    } else {
      int32_t node = 0;
      for (;;) {
        const int32_t gi = nb + node;
        const double v = row[split_feature[gi]];
        const uint8_t dt = decision_type[gi];
        bool go_left;
        if (dt & 1) {  // categorical: bitset membership, NaN goes right
          go_left = false;
          if (!std::isnan(v)) {
            const int64_t iv = static_cast<int64_t>(v);
            if (iv >= 0) {
              const int32_t k = threshold_bin[gi];  // cat ordinal
              const int32_t s = cat_boundaries[k], e = cat_boundaries[k + 1];
              const int64_t word = iv / 32;
              if (word < e - s)
                go_left = (cat_threshold[s + word] >> (iv % 32)) & 1u;
            }
          }
        } else {
          const int mt = (dt >> 2) & 3;
          const bool miss =
              std::isnan(v) || (mt == 1 && std::fabs(v) < 1e-35);
          go_left = miss ? ((dt & 2) != 0) : (v <= threshold[gi]);
        }
        const int32_t nxt = go_left ? left[gi] : right[gi];
        if (nxt < 0) { leaf = leaf_value[leaf_off[t] + (~nxt)]; break; }
        node = nxt;
      }
    }
    out[t % num_class] += leaf;
  }
}

}  // extern "C"
