// Native host-side micro-batch prep: dedup + pack in two linear passes.
//
// The serving loop's host stage (runtime/engine.py::_start_batch) does
// latest-wins dedup by tx_id, key folding, µs-epoch splitting, cents→f32
// amounts, and the single-array packing of core/batch.py::pack_batch.
// The NumPy pipeline for that runs ~3.2M rows/s on one CPU core, which a
// locally attached chip can outrun. This unit is
// the same math as the NumPy path, one pass each, allocation-free:
//
//   latest_wins_keep — reference ROW_NUMBER() PARTITION BY tx_id ORDER BY
//     ts DESC semantics (kafka_s3_sink_transactions.py:173-190): for each
//     key keep the row with the greatest (ts, position). Open-addressing
//     hash, O(n). Bit-identical masks to ops/dedup.latest_wins_mask_np
//     (differential-fuzz-pinned in tests/test_native.py).
//
//   pack_rows — the fused make_batch + pack_batch: fold_key xor-fold,
//     floor day/second-of-day split, (double)cents/100 → float amounts
//     (same IEEE ops as NumPy's float64-divide-then-float32-cast), label
//     or -1, valid flags; zeros in the padding tail. Output layout is
//     core/batch.pack_batch's [7, pad] int32.
//
//   pack_rows_wide — the same at key_bits=64: an id is split into its two
//     words instead of folded (low words in rows 0-1, high words in rows
//     7-8 of core/batch.pack_batch's [9, pad] int32).
//
// Build: g++ -O3 -shared -fPIC -o libhostprep.so hostprep.cc

#include <cstdint>
#include <cstring>
#include <vector>

namespace {

inline uint64_t mix64(uint64_t x) {
  x ^= x >> 33;
  x *= 0xff51afd7ed558ccdULL;
  x ^= x >> 33;
  x *= 0xc4ceb9fe1a85ec53ULL;
  x ^= x >> 33;
  return x;
}

// packed: int32 [7, pad] C-order, or [9, pad] with the keys' high words
// in rows 7-8 (Wide). label may be NULL (=> -1 everywhere).
template <bool Wide>
void pack_rows_impl(const int64_t* dt_us, const int64_t* cust,
                    const int64_t* term, const int64_t* amount,
                    const int64_t* label, int64_t n, int64_t pad,
                    int32_t* packed) {
  const int64_t kUsPerDay = 86400000000LL;
  int32_t* ck = packed;
  int32_t* tk = packed + pad;
  int32_t* day = packed + 2 * pad;
  int32_t* tod = packed + 3 * pad;
  int32_t* amt = packed + 4 * pad;
  int32_t* lab = packed + 5 * pad;
  int32_t* val = packed + 6 * pad;
  int32_t* ch = packed + 7 * pad;  // Wide only
  int32_t* th = packed + 8 * pad;
  std::memset(packed, 0, sizeof(int32_t) * (Wide ? 9 : 7) * (size_t)pad);
  for (int64_t i = 0; i < n; ++i) {
    uint64_t c = (uint64_t)cust[i];
    uint64_t t = (uint64_t)term[i];
    if (Wide) {
      ck[i] = (int32_t)(uint32_t)(c & 0xFFFFFFFFULL);
      ch[i] = (int32_t)(uint32_t)(c >> 32);
      tk[i] = (int32_t)(uint32_t)(t & 0xFFFFFFFFULL);
      th[i] = (int32_t)(uint32_t)(t >> 32);
    } else {
      ck[i] = (int32_t)(uint32_t)((c ^ (c >> 32)) & 0xFFFFFFFFULL);
      tk[i] = (int32_t)(uint32_t)((t ^ (t >> 32)) & 0xFFFFFFFFULL);
    }
    int64_t d = dt_us[i] / kUsPerDay;
    int64_t r = dt_us[i] % kUsPerDay;
    if (r < 0) {  // match NumPy floor-division semantics
      d -= 1;
      r += kUsPerDay;
    }
    day[i] = (int32_t)d;
    tod[i] = (int32_t)(r / 1000000LL);
    float a = (float)((double)amount[i] / 100.0);
    std::memcpy(&amt[i], &a, 4);
    lab[i] = label ? (int32_t)label[i] : -1;
    val[i] = 1;
  }
}

}  // namespace

extern "C" {

// keep[i]=1 where row i is the latest version of its key. Returns the
// number of kept rows. Ties on ts resolve to the later position (i > cur
// always holds when revisiting a key).
int64_t latest_wins_keep(const int64_t* key, const int64_t* ts, int64_t n,
                         uint8_t* keep) {
  if (n <= 0) return 0;
  uint64_t cap = 1;
  while (cap < (uint64_t)n * 2) cap <<= 1;
  std::vector<int64_t> slot(cap, -1);
  std::memset(keep, 0, (size_t)n);
  const uint64_t mask = cap - 1;
  const int64_t kSentinel = INT64_MIN;
  for (int64_t i = 0; i < n; ++i) {
    // parity with the NumPy mask: INT64_MIN doubles as its invalid-row
    // sentinel, so rows carrying that key are never kept there either
    if (key[i] == kSentinel) continue;
    uint64_t j = mix64((uint64_t)key[i]) & mask;
    for (;;) {
      int64_t cur = slot[j];
      if (cur < 0) {
        slot[j] = i;
        keep[i] = 1;
        break;
      }
      if (key[cur] == key[i]) {
        if (ts[i] >= ts[cur]) {
          keep[cur] = 0;
          keep[i] = 1;
          slot[j] = i;
        }
        break;
      }
      j = (j + 1) & mask;
    }
  }
  int64_t kept = 0;
  for (int64_t i = 0; i < n; ++i) kept += keep[i];
  return kept;
}

void pack_rows(const int64_t* dt_us, const int64_t* cust,
               const int64_t* term, const int64_t* amount,
               const int64_t* label, int64_t n, int64_t pad,
               int32_t* packed) {
  pack_rows_impl<false>(dt_us, cust, term, amount, label, n, pad, packed);
}

void pack_rows_wide(const int64_t* dt_us, const int64_t* cust,
                    const int64_t* term, const int64_t* amount,
                    const int64_t* label, int64_t n, int64_t pad,
                    int32_t* packed) {
  pack_rows_impl<true>(dt_us, cust, term, amount, label, n, pad, packed);
}

}  // extern "C"
