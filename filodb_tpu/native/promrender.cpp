// Prometheus JSON sample-array renderer (the serving-edge hot loop).
//
// Renders one series' samples as the JSON fragment
//     [[<t_seconds>,"<value>"],[...],...]
// skipping NaN samples (Prometheus absence). Timestamps render in fixed
// 3-decimal seconds (Prometheus' millisecond convention, e.g.
// 1600000000.000). Values render byte-identically to CPython's
// repr(float(v)) — shortest round-trip decimal with repr's fixed/scientific
// switch (-4 <= e10 < 16), integral values carrying a ".0" suffix — so the
// native fragment is byte-for-byte the Python fallback's output
// (api/promjson.py golden-asserts this). Specials render as "+Inf"/"-Inf".
// The f32 variant widens to double first — identical to float(np.float32(x)).
//
// The shortest-repr search is hand-rolled because this container's gcc 10
// libstdc++ ships integer std::to_chars but not the float overload: a
// double-long-double (Dekker) scaling by a ~128-bit power-of-10 table
// produces the 17-digit decimal plus an error term tight enough (~1e-21)
// to probe shorter candidates against the exact round-trip interval
// [v - ulp_down/2, v + ulp_up/2]. The interval is asymmetric at powers of
// two, so candidates are tested against each half-width rather than by
// distance alone. Ambiguous cases (genuine decimal ties near *.5, interval
// edges within 1e-9 ulp17) fall back to a snprintf/strtod probe loop that
// also tries the last-digit neighbour on the far side — near pow2
// boundaries the nearest k-digit decimal can fail the round trip while the
// neighbour passes. Fallback rate is ~0.6% on f32-widened data, ~0 on f64.
//
// Reference analog: prometheus/.../query/PrometheusModel.scala:256 (the JVM
// circe render).
//
// Build: g++ -O3 -march=native -std=c++17 -shared -fPIC promrender.cpp \
//        -o libfilodbrender.so

#include <cfloat>
#include <charconv>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>

namespace {

// two-digit-pair lookup: halves the division chain in the hot itoa loops
// ("00".."99" as 200 contiguous bytes)
constexpr char kDigitPairs[201] =
    "00010203040506070809101112131415161718192021222324"
    "25262728293031323334353637383940414243444546474849"
    "50515253545556575859606162636465666768697071727374"
    "75767778798081828384858687888990919293949596979899";

inline char* emit_u64(char* p, unsigned long long v) {
  char tmp[20];
  char* q = tmp + 20;
  while (v >= 100) {
    unsigned d = unsigned(v % 100) * 2;
    v /= 100;
    *--q = kDigitPairs[d + 1];
    *--q = kDigitPairs[d];
  }
  if (v >= 10) {
    unsigned d = unsigned(v) * 2;
    *--q = kDigitPairs[d + 1];
    *--q = kDigitPairs[d];
  } else {
    *--q = char('0' + v);
  }
  std::memcpy(p, q, tmp + 20 - q);
  return p + (tmp + 20 - q);
}

// fixed 3-decimal seconds from a seconds-as-double timestamp; ~4x the
// throughput of shortest-form and format-stable across platforms.
// Matches the Python fallback's sign + magnitude-of-truncating-div/mod form
// exactly (llround = round-half-away; promjson._ts3).
inline char* render_ts(char* p, double t_sec) {
  long long ms = llround(t_sec * 1000.0);
  long long sec = ms / 1000;
  long long frac = ms % 1000;
  if (ms < 0) {  // pre-epoch: render sign, then magnitude
    *p++ = '-';
    sec = -sec;
    frac = -frac;
  }
  p = emit_u64(p, (unsigned long long)sec);
  *p++ = '.';
  unsigned d = unsigned(frac / 10) * 2;  // frac < 1000
  *p++ = kDigitPairs[d];
  *p++ = kDigitPairs[d + 1];
  *p++ = char('0' + frac % 10);
  return p;
}

// ---- shortest round-trip digits, repr()-identical --------------------------

// double-long-double helpers (Dekker two_prod / two_sum on the 64-bit
// x87 mantissa)
const long double kLdSplit = 4294967297.0L;  // 2^32 + 1

inline void dd_two_prod(long double a, long double b, long double* hi,
                        long double* lo) {
  long double p = a * b;
  long double t = kLdSplit * a, ahi = t - (t - a), alo = a - ahi;
  t = kLdSplit * b;
  long double bhi = t - (t - b), blo = b - bhi;
  *hi = p;
  *lo = ((ahi * bhi - p) + ahi * blo + alo * bhi) + alo * blo;
}

inline void dd_two_sum(long double a, long double b, long double* hi,
                       long double* lo) {
  long double s = a + b, v = s - a;
  *hi = s;
  *lo = (a - (s - v)) + (b - v);
}

// dd power-of-10 table: P10H[i] + P10L2[i] ~= 10^(i-350) to ~128 bits
long double P10H[701], P10L2[701];

void p10_init() {
  P10H[350] = 1.0L;
  P10L2[350] = 0.0L;
  for (int n = 1; n <= 350; n++) {
    long double h, l, h2, l2;
    dd_two_prod(P10H[350 + n - 1], 10.0L, &h, &l);
    l += P10L2[350 + n - 1] * 10.0L;
    dd_two_sum(h, l, &h2, &l2);
    P10H[350 + n] = h2;
    P10L2[350 + n] = l2;
    // negative powers: dd division by the exactly-representable 10 via
    // quotient + residual correction
    long double q = P10H[350 - n + 1] / 10.0L;
    long double ph, pl;
    dd_two_prod(q, 10.0L, &ph, &pl);
    long double r = ((P10H[350 - n + 1] - ph) - pl) + P10L2[350 - n + 1];
    long double qlo = r / 10.0L;
    dd_two_sum(q, qlo, &h2, &l2);
    P10H[350 - n] = h2;
    P10L2[350 - n] = l2;
  }
}

const bool g_p10_ready = (p10_init(), true);  // runs at dlopen

const uint64_t POW10[18] = {1ull,
                            10ull,
                            100ull,
                            1000ull,
                            10000ull,
                            100000ull,
                            1000000ull,
                            10000000ull,
                            100000000ull,
                            1000000000ull,
                            10000000000ull,
                            100000000000ull,
                            1000000000000ull,
                            10000000000000ull,
                            100000000000000ull,
                            1000000000000000ull,
                            10000000000000000ull,
                            100000000000000000ull};

long g_slow_count = 0;

// slow-path helper: does the k-digit decimal D * 10^(e10-k+1) parse back to
// av? On success strips trailing zeros into digits/e10_out.
bool parse_eq(uint64_t D, int k, int e10, double av, char* digits,
              int* e10_out, int* nd_out) {
  char tmp[24], buf[48];
  auto r = std::to_chars(tmp, tmp + sizeof tmp, D);
  if ((int)(r.ptr - tmp) != k) return false;
  char* p = buf;
  *p++ = tmp[0];
  if (k > 1) {
    *p++ = '.';
    std::memcpy(p, tmp + 1, k - 1);
    p += k - 1;
  }
  *p++ = 'e';
  p += snprintf(p, 8, "%d", e10);
  *p = 0;
  if (strtod(buf, nullptr) != av) return false;
  int nd = k;
  while (nd > 1 && tmp[nd - 1] == '0') nd--;
  std::memcpy(digits, tmp, nd);
  *e10_out = e10;
  *nd_out = nd;
  return true;
}

// reference slow path: snprintf probing. For each digit count k, tries the
// correctly-rounded candidate AND its last-digit neighbour on the other side
// of av: near asymmetric ulp boundaries (powers of two) the nearest k-digit
// decimal can fail the round trip while the farther neighbour passes.
int slow_digits(double av, char* digits, int* e10_out) {
  char buf[64];
  for (int k = 1; k <= 17; k++) {
    snprintf(buf, sizeof buf, "%.*e", k - 1, av);
    double sv = strtod(buf, nullptr);
    uint64_t D = 0;
    for (const char* p = buf; *p && *p != 'e'; p++)
      if (*p >= '0' && *p <= '9') D = D * 10 + (uint64_t)(*p - '0');
    int e10 = atoi(strchr(buf, 'e') + 1);
    int nd;
    if (sv == av) {
      char tmp[24];
      std::to_chars(tmp, tmp + sizeof tmp, D);
      nd = k;
      while (nd > 1 && tmp[nd - 1] == '0') nd--;
      std::memcpy(digits, tmp, nd);
      *e10_out = e10;
      return nd;
    }
    if (sv < av) {
      if (D + 1 >= POW10[k]) {  // 999... carries into the next decade
        if (parse_eq(POW10[k - 1], k, e10 + 1, av, digits, e10_out, &nd))
          return nd;
      } else if (parse_eq(D + 1, k, e10, av, digits, e10_out, &nd)) {
        return nd;
      }
    } else if (D > POW10[k - 1]) {
      if (parse_eq(D - 1, k, e10, av, digits, e10_out, &nd)) return nd;
    }
  }
  return 0;
}

// fast path: dd scaling + integer candidate probing against the round-trip
// interval. Returns digit count, or -1 when a guard band is hit and the
// answer must come from slow_digits.
int fast_digits(double av, char* digits, int* e10_out) {
  if (LDBL_MANT_DIG < 64) return -1;  // needs the x87 64-bit mantissa
  int e2;
  (void)frexp(av, &e2);
  int e10 = (int)floor((e2 - 1) * 0.3010299956639812);
  if (e10 < -280 || e10 > 280) return -1;  // subnormal/extreme: slow path
  int i = 366 - e10;  // table index for 10^(16-e10)
  long double L, Le, t;
  dd_two_prod((long double)av, P10H[i], &L, &t);
  Le = t + (long double)av * P10L2[i];
  for (int k = 0; k < 3 && (L < 1e16L || L >= 1e17L); k++) {
    e10 += (L >= 1e17L) ? 1 : -1;
    if (e10 < -280 || e10 > 280) return -1;
    i = 366 - e10;
    dd_two_prod((long double)av, P10H[i], &L, &t);
    Le = t + (long double)av * P10L2[i];
  }
  if (L < 1e16L || L >= 1e17L) return -1;

  const long double GTIE = 1e-9L;  // >> dd error (~1e-21), << real margins
  uint64_t D17 = (uint64_t)(L + 0.5L);
  long double f17 = (L - (long double)D17) + Le;  // L_true - D17
  if (f17 >= 0.5L) {
    D17++;
    f17 -= 1.0L;
  } else if (f17 < -0.5L) {
    D17--;
    f17 += 1.0L;
  }
  // genuine decimal tie at the 17th digit (f32-widened data hits these)
  if (fabsl(fabsl(f17) - 0.5L) < GTIE) return -1;
  if (D17 < POW10[16] || D17 >= POW10[17]) return -1;

  // round-trip interval half-widths in ulp17 units (asymmetric at pow2)
  uint64_t ab;
  std::memcpy(&ab, &av, 8);
  double up, dn;
  uint64_t ub = ab + 1, db = ab - 1;
  std::memcpy(&up, &ub, 8);
  std::memcpy(&dn, &db, 8);
  long double hu = (long double)(up - av) * 0.5L * P10H[i];
  long double hd = (long double)(av - dn) * 0.5L * P10H[i];

  uint64_t flo = (f17 < 0) ? D17 - 1 : D17;
  uint64_t Dbest = D17;
  int jbest = 17, ebest = e10;
  for (int j = 16; j >= 1; j--) {
    uint64_t q = POW10[17 - j];
    uint64_t c1 = flo - flo % q;  // floor candidate at j digits
    uint64_t c2 = c1 + q;        // ceil candidate
    long double o1 = (long double)(int64_t)(c1 - D17) - f17;  // <= 0
    long double o2 = (long double)(int64_t)(c2 - D17) - f17;  // > 0
    bool ok1 = -o1 < hd, ok2 = o2 < hu;
    if (fabsl(-o1 - hd) < GTIE || fabsl(o2 - hu) < GTIE) return -1;
    if (!ok1 && !ok2) break;  // monotone: shorter can't round-trip either
    uint64_t D;
    if (ok1 && ok2) {
      if (fabsl(-o1 - o2) < GTIE) return -1;  // equidistant candidates
      D = (-o1 < o2) ? c1 : c2;
    } else {
      D = ok1 ? c1 : c2;
    }
    if (D >= POW10[17]) {  // ceil carried to 10^17: one digit, next decade
      Dbest = POW10[16];
      jbest = 17;
      ebest = e10 + 1;
    } else {
      Dbest = D / q;
      jbest = j;
      ebest = e10;
      if (Dbest >= POW10[j]) {  // in-decade carry (e.g. 999 -> 100, e+1)
        Dbest /= 10;
        ebest = e10 + 1;
      }
    }
  }
  while (jbest > 1 && Dbest % 10 == 0) {
    Dbest /= 10;
    jbest--;
  }
  char tmp[24];
  auto r = std::to_chars(tmp, tmp + sizeof tmp, Dbest);
  int len = (int)(r.ptr - tmp);
  std::memcpy(digits, tmp, len);
  *e10_out = ebest;
  return len;
}

// digits + decimal exponent -> repr() surface form: fixed for -4 <= e10 < 16
// (integral magnitudes carry ".0"), scientific d[.ddd]e±NN otherwise.
int format_repr(bool neg, const char* digits, int nd, int e10, char* out) {
  char* p = out;
  if (neg) *p++ = '-';
  if (-4 <= e10 && e10 < 16) {
    if (e10 >= nd - 1) {
      std::memcpy(p, digits, nd);
      p += nd;
      for (int i = 0; i < e10 - nd + 1; i++) *p++ = '0';
      *p++ = '.';
      *p++ = '0';
    } else if (e10 >= 0) {
      std::memcpy(p, digits, e10 + 1);
      p += e10 + 1;
      *p++ = '.';
      std::memcpy(p, digits + e10 + 1, nd - e10 - 1);
      p += nd - e10 - 1;
    } else {
      *p++ = '0';
      *p++ = '.';
      for (int i = 0; i < -e10 - 1; i++) *p++ = '0';
      std::memcpy(p, digits, nd);
      p += nd;
    }
  } else {
    *p++ = digits[0];
    if (nd > 1) {
      *p++ = '.';
      std::memcpy(p, digits + 1, nd - 1);
      p += nd - 1;
    }
    *p++ = 'e';
    *p++ = e10 < 0 ? '-' : '+';
    unsigned ae = e10 < 0 ? -e10 : e10;
    if (ae < 10) {  // repr pads the exponent to two digits
      *p++ = '0';
      *p++ = (char)('0' + ae);
    } else {
      auto rr = std::to_chars(p, p + 8, ae);
      p = rr.ptr;
    }
  }
  return (int)(p - out);
}

// finite, non-zero v -> repr(float(v)) bytes
inline char* render_value(char* p, double v) {
  bool neg = std::signbit(v);
  double av = neg ? -v : v;
  if (av < 1e16) {  // integral fast path: repr gives digits + ".0"
    double r = std::nearbyint(av);
    if (r == av) {
      if (neg) *p++ = '-';
      p = emit_u64(p, (unsigned long long)r);
      *p++ = '.';
      *p++ = '0';
      return p;
    }
  }
  char digits[24];
  int e10;
  int nd = fast_digits(av, digits, &e10);
  if (nd <= 0) {
    g_slow_count++;
    nd = slow_digits(av, digits, &e10);
  }
  return p + format_repr(neg, digits, nd, e10, p);
}

long render(const double* ts, const double* vals_d, const float* vals_f,
            long n, char* out, long cap) {
  char* p = out;
  char* e = out + cap;
  if (e - p < 2) return -1;
  *p++ = '[';
  bool first = true;
  for (long i = 0; i < n; i++) {
    double v = vals_d ? vals_d[i] : (double)vals_f[i];
    if (std::isnan(v)) continue;
    if (e - p < 64) return -1;
    if (!first) *p++ = ',';
    first = false;
    *p++ = '[';
    p = render_ts(p, ts[i]);
    *p++ = ',';
    *p++ = '"';
    if (std::isinf(v)) {
      std::memcpy(p, v > 0 ? "+Inf" : "-Inf", 4);
      p += 4;
    } else if (v == 0.0) {
      if (std::signbit(v)) *p++ = '-';
      std::memcpy(p, "0.0", 3);
      p += 3;
    } else {
      p = render_value(p, v);
    }
    *p++ = '"';
    *p++ = ']';
  }
  if (e - p < 1) return -1;
  *p++ = ']';
  return p - out;
}

}  // namespace

extern "C" {

// repr(float(v)) bytes into out (>= 32 bytes); returns length. Specials use
// repr's own names (nan/inf/-inf) — the JSON layer maps its NaN/+Inf/-Inf
// before reaching here. Exposed for the byte-parity torture test.
int fdb_format_double(double v, char* out) {
  if (std::isnan(v)) {
    std::memcpy(out, "nan", 3);
    return 3;
  }
  if (std::isinf(v)) {
    if (v > 0) {
      std::memcpy(out, "inf", 3);
      return 3;
    }
    std::memcpy(out, "-inf", 4);
    return 4;
  }
  if (v == 0.0) {
    bool neg = std::signbit(v);
    std::memcpy(out, neg ? "-0.0" : "0.0", 4);
    return neg ? 4 : 3;
  }
  char* p = render_value(out, v);
  return (int)(p - out);
}

// diagnostic: how many values fell through to the snprintf/strtod slow path
long fdb_fmt_slow_count() { return g_slow_count; }

long fdb_render_values_f64(const double* ts, const double* vals, long n,
                           char* out, long cap) {
  return render(ts, vals, nullptr, n, out, cap);
}

long fdb_render_values_f32(const double* ts, const float* vals, long n,
                           char* out, long cap) {
  return render(ts, nullptr, vals, n, out, cap);
}

// [G,J] matrix -> G per-series fragments written back-to-back into out.
// offsets (length G+1) gets each fragment's start byte; offsets[G] = total.
// Returns total bytes, or -1 if cap is too small.
long long fdb_render_matrix_f64(const double* ts, const double* vals,
                                long long G, long long J, char* out,
                                long long cap, long long* offsets) {
  char* p = out;
  for (long long g = 0; g < G; g++) {
    offsets[g] = p - out;
    long w = render(ts, vals + g * J, nullptr, (long)J, p,
                    (long)(out + cap - p));
    if (w < 0) return -1;
    p += w;
  }
  offsets[G] = p - out;
  return p - out;
}

long long fdb_render_matrix_f32(const double* ts, const float* vals,
                                long long G, long long J, char* out,
                                long long cap, long long* offsets) {
  char* p = out;
  for (long long g = 0; g < G; g++) {
    offsets[g] = p - out;
    long w = render(ts, nullptr, vals + g * J, (long)J, p,
                    (long)(out + cap - p));
    if (w < 0) return -1;
    p += w;
  }
  offsets[G] = p - out;
  return p - out;
}
}
