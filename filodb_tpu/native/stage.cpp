// Cold histogram staging, the host half (ops/staging.py stage_from_shard):
// one pass over a shard's chunk segments writes the padded [S, T(, B)] block
// that stage_histogram_series builds with two Python loops, bit for bit.
//
// The caller hands over a table of segments, one row each, sorted by block
// row and, within a row, in time order (sealed chunks, then the write
// buffer). The arrays a row names stay alive on the Python side until the
// call has returned; rows below the snapshotted length are never rewritten.
//
// Build: g++ -O3 -march=native -shared -fPIC stage.cpp -o libfilodbstage.so

#include <cstdint>
#include <vector>

namespace {

// must mirror native/__init__.py STAGE_SEG_COLS
struct Seg {
    int64_t row;    // block row (series) the samples go to
    int64_t ts;     // address of int64[n] timestamps, ascending
    int64_t vals;   // address of [n, B] values, C order
    int64_t n;      // rows readable behind both addresses
    int64_t clamp;  // samples before this ms belong to an earlier segment
    int64_t flags;  // INT_VALUES | GATED
    int64_t lo;     // out (measure): first row in range
    int64_t k;      // out (measure): rows in range
};

const int64_t INT_VALUES = 1;  // values are int64 (a decoded chunk), else f64
const int64_t GATED = 2;       // write buffer: skipped unless first/last overlap

const int32_t TS_PAD = 2147483647;

long lower_bound(const int64_t* a, long n, int64_t x) {
    long lo = 0, hi = n;
    while (lo < hi) {
        long mid = lo + (hi - lo) / 2;
        if (a[mid] < x) lo = mid + 1; else hi = mid;
    }
    return lo;
}

// one segment's rows into the block; `b` is the row's f64 baseline (or null)
template <typename V>
void put_values(const V* v, long k, long B, const double* b, float* out) {
    if (b == nullptr) {
        for (long i = 0; i < k * B; i++) out[i] = (float)v[i];
        return;
    }
    for (long i = 0; i < k; i++)
        for (long j = 0; j < B; j++)
            out[i * B + j] = (float)((double)v[i * B + j] - b[j]);
}

}  // namespace

extern "C" {

// Pass 1: each segment's [lo, lo + k) by binary search, each row's length
// into lens[0..S). Returns the longest row, or -1 for a table it refuses.
long fdb_stage_measure(int64_t* table, long nseg, int64_t t0, int64_t t1,
                       int32_t* lens, long S) {
    Seg* seg = (Seg*)table;
    for (long r = 0; r < S; r++) lens[r] = 0;
    long longest = 0, prev_row = 0;
    for (long s = 0; s < nseg; s++) {
        Seg& g = seg[s];
        if (g.row < prev_row || g.row >= S || g.n < 0) return -1;
        prev_row = g.row;
        const int64_t* ts = (const int64_t*)g.ts;
        g.lo = g.k = 0;
        if (g.n == 0) continue;
        if ((g.flags & GATED) && (ts[g.n - 1] < t0 || ts[0] > t1)) continue;
        long lo = lower_bound(ts, g.n, t0 > g.clamp ? t0 : g.clamp);
        long hi = lower_bound(ts, g.n, t1 + 1);
        if (hi <= lo) continue;
        g.lo = lo;
        g.k = hi - lo;
        lens[g.row] += (int32_t)g.k;
        if (lens[g.row] > longest) longest = lens[g.row];
    }
    return longest;
}

// Pass 2: every element of out_ts [S, T], out_vals [S, T, B] and
// baseline [S, B] is written exactly once: samples, then the pads.
// `subtract` stages values minus the row's FIRST sample in range (in f64,
// cast once), and that sample, cast, is the row's baseline.
long fdb_stage_fill(const int64_t* table, long nseg, long S, long T, long B,
                    int64_t base_ms, int subtract, const int32_t* lens,
                    int32_t* out_ts, float* out_vals, float* baseline) {
    const Seg* seg = (const Seg*)table;
    std::vector<double> base(B);
    double* b = base.data();
    long s = 0;
    for (long r = 0; r < S; r++) {
        int32_t* row_ts = out_ts + r * T;
        float* row_vals = out_vals + r * T * B;
        float* row_base = baseline + r * B;
        long m = 0;
        bool based = false;
        for (; s < nseg && seg[s].row == r; s++) {
            const Seg& g = seg[s];
            if (g.k == 0) continue;
            if (m + g.k > T) return -1;
            const int64_t* ts = (const int64_t*)g.ts + g.lo;
            for (long i = 0; i < g.k; i++)
                row_ts[m + i] = (int32_t)(ts[i] - base_ms);
            const bool ints = g.flags & INT_VALUES;
            const double* vf = (const double*)g.vals + g.lo * B;
            const int64_t* vi = (const int64_t*)g.vals + g.lo * B;
            if (subtract && !based) {
                for (long j = 0; j < B; j++) {
                    b[j] = ints ? (double)vi[j] : vf[j];
                    row_base[j] = (float)b[j];
                }
                based = true;
            }
            float* out = row_vals + m * B;
            if (ints) put_values(vi, g.k, B, subtract ? b : nullptr, out);
            else put_values(vf, g.k, B, subtract ? b : nullptr, out);
            m += g.k;
        }
        if (m != lens[r]) return -1;
        if (!based)
            for (long j = 0; j < B; j++) row_base[j] = 0.0f;
        for (long i = m; i < T; i++) row_ts[i] = TS_PAD;
        for (long i = m * B; i < T * B; i++) row_vals[i] = 0.0f;
    }
    return 0;
}

}  // extern "C"
