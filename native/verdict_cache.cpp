// Native verdict cache + within-batch row dedup for the native lane.
//
// One structure answers both ends of a cut (runtime/native_frontend.py):
//   plan    reads the cut's rows where the C++ encoder wrote them, probes
//           the cache for the eligible ones, collapses the misses to unique
//           rows, and copies the keys of the eligible unique misses into a
//           ticket (the slot may be refilled before the verdicts are back);
//   resolve decodes the cut's launch results and fans them out through the
//           plan to every row of the cut;
//   commit  inserts a ticket's keys with the verdicts of their rows.
// All run without the interpreter lock; plan and commit take the cache's one
// mutex only then (pymod.cpp releases the lock before it calls in here).
//
// The contract is utils/verdict_cache.py + compiler/pack.py dedup_rows,
// which stay the engine lane's and the tests' reference: a key is the token
// and the row's operand bytes, compared byte for byte on every hit (the hash
// picks a bucket, it is never the key); the LRU is exact over at most `cap`
// entries (a hit moves the entry to the young end, an insert past the bound
// evicts the oldest, an insert of a present key refreshes value and place);
// ineligible rows are neither probed nor inserted; the dedup keeps the first
// occurrence and submission order.
//
// Compiled as part of the _atpuenc single translation unit (pymod.cpp).

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <mutex>
#include <string>
#include <vector>

namespace vc {

// one operand array of the slot: row r < rows has its bytes at
// [base + r*bytes, +bytes).  `used` 0: the whole row is key.  Otherwise it is
// the address of a u16 a row, and the row is `sub` sub-rows `sub_stride`
// bytes apart of which only the first used[r] bytes of each are key: the
// writer guarantees every byte past them is zero (the byte lane's
// [NB, W] row, native/frontend.cpp Slot::byte_used), so two rows are equal
// exactly where their keys are, and a row's key is as long as its longest
// value and not as wide as the lane.
struct Seg { uint64_t base, bytes, rows, sub, sub_stride, used; };

inline uint64_t hash_bytes(const uint8_t* p, size_t n) {
  uint64_t h = 0x9E3779B97F4A7C15ull ^ (n * 0xFF51AFD7ED558CCDull);
  for (; n >= 8; p += 8, n -= 8) {
    uint64_t w;
    memcpy(&w, p, 8);
    h = (h ^ w) * 0x9FB21C651E98DF25ull;
    h ^= h >> 29;
  }
  if (n) {
    uint64_t w = 0;
    memcpy(&w, p, n);
    h = (h ^ w) * 0x9FB21C651E98DF25ull;
    h ^= h >> 29;
  }
  h ^= h >> 32;
  h *= 0xD6E8FEB86659FD93ull;
  return h ^ (h >> 32);
}

inline uint64_t mix_token(uint64_t h, uint64_t token) {
  h ^= token * 0xC2B2AE3D27D4EB4Full;
  h *= 0x9FB21C651E98DF25ull;
  return h ^ (h >> 31);
}

struct Cache {
  struct Entry {
    uint64_t token = 0, hash = 0;
    int32_t hnext = -1;              // bucket chain
    int32_t older = -1, younger = -1;  // LRU list
    int32_t firing = -1;
    uint8_t verdict = 0;
    std::string key;                 // capacity reused across evictions
  };

  const int32_t cap;
  uint64_t mask;
  std::mutex mu;
  std::vector<int32_t> head;   // bucket -> newest entry of its chain, or -1
  std::vector<Entry> e;        // grows to cap, then entries are recycled
  int32_t oldest = -1, youngest = -1;
  // written under mu, read without it by counts()
  std::atomic<uint64_t> hits{0}, misses{0}, adds{0}, evictions{0}, entries{0};

  // buckets 0 = two a possible entry; a caller's own count (rounded up to a
  // power of two) lets a test force every key into one chain
  Cache(int32_t max_entries, int64_t buckets) : cap(max_entries) {
    uint64_t want = buckets > 0 ? (uint64_t)buckets : (uint64_t)cap * 2;
    uint64_t nb = 1;
    while (nb < want) nb <<= 1;
    mask = nb - 1;
    head.assign(nb, -1);
  }

  static void bump(std::atomic<uint64_t>& c, uint64_t by = 1) {
    c.store(c.load(std::memory_order_relaxed) + by, std::memory_order_relaxed);
  }

  int32_t find(uint64_t token, uint64_t hash, const uint8_t* key, size_t len) const {
    for (int32_t i = head[hash & mask]; i >= 0; i = e[i].hnext) {
      const Entry& x = e[i];
      if (x.hash == hash && x.token == token && x.key.size() == len &&
          memcmp(x.key.data(), key, len) == 0)
        return i;
    }
    return -1;
  }

  void unlink_lru(int32_t i) {
    Entry& x = e[i];
    if (x.older >= 0) e[x.older].younger = x.younger; else oldest = x.younger;
    if (x.younger >= 0) e[x.younger].older = x.older; else youngest = x.older;
  }

  void push_young(int32_t i) {
    Entry& x = e[i];
    x.older = youngest;
    x.younger = -1;
    if (youngest >= 0) e[youngest].younger = i; else oldest = i;
    youngest = i;
  }

  void touch(int32_t i) {
    if (i == youngest) return;
    unlink_lru(i);
    push_young(i);
  }

  void unlink_bucket(int32_t i) {
    int32_t* at = &head[e[i].hash & mask];
    while (*at != i) at = &e[*at].hnext;
    *at = e[i].hnext;
  }

  // VerdictCache._put; returns 1 when it evicted
  int put(uint64_t token, uint64_t hash, const uint8_t* key, size_t len,
          uint8_t verdict, int32_t firing) {
    int32_t i = find(token, hash, key, len);
    if (i >= 0) {
      touch(i);
      e[i].verdict = verdict;
      e[i].firing = firing;
      return 0;
    }
    int evicted = 0;
    if ((int32_t)e.size() < cap) {
      e.emplace_back();
      i = (int32_t)e.size() - 1;
      bump(entries);
    } else {
      i = oldest;
      unlink_lru(i);
      unlink_bucket(i);
      bump(evictions);
      evicted = 1;
    }
    Entry& x = e[i];
    x.token = token;
    x.hash = hash;
    x.key.assign((const char*)key, len);
    x.verdict = verdict;
    x.firing = firing;
    x.hnext = head[hash & mask];
    head[hash & mask] = i;
    push_young(i);
    bump(adds);
    return evicted;
  }
};

// keys of a cut's eligible unique misses, as they were at plan time
struct Ticket {
  int32_t count = 0;               // rows of the cut: bounds commit's reads
  std::vector<uint8_t> keys;       // key i: [at[i], at[i+1])
  std::vector<size_t> at;
  std::vector<uint64_t> tokens, hashes;
  std::vector<int32_t> rows;       // the cut's row whose verdict is the value
};

struct Plan {
  // [cached rows | their verdicts | their firing columns | miss rows |
  //  unique rows | inverse (one a miss row, into unique rows)]
  std::vector<int32_t> out;
  int32_t n_cached = 0, n_miss = 0, n_unique = 0, elig_miss = 0;
};

inline void plan(Cache* cache, const Seg* segs, size_t nseg, int32_t count,
                 const uint64_t* tokens, const uint8_t* eligible, bool dedup,
                 Plan& p, Ticket& t) {
  static thread_local std::vector<uint8_t> rows_buf;
  static thread_local std::vector<uint64_t> hb;
  static thread_local std::vector<size_t> koff;  // row r's key: [koff[r], koff[r+1])
  koff.resize((size_t)count + 1);
  koff[0] = 0;
  for (int32_t r = 0; r < count; ++r) {
    size_t w = 0;
    for (size_t s = 0; s < nseg; ++s)
      w += segs[s].used ? segs[s].sub * ((const uint16_t*)segs[s].used)[r] : segs[s].bytes;
    koff[r + 1] = koff[r] + w;
  }
  rows_buf.resize(koff[count]);
  hb.resize(count);
  uint8_t* rows = rows_buf.data();
  for (int32_t r = 0; r < count; ++r) {
    uint8_t* dst = rows + koff[r];
    for (size_t s = 0; s < nseg; ++s) {
      const uint8_t* src = (const uint8_t*)segs[s].base + (size_t)r * segs[s].bytes;
      if (!segs[s].used) {
        memcpy(dst, src, segs[s].bytes);
        dst += segs[s].bytes;
        continue;
      }
      const size_t u = ((const uint16_t*)segs[s].used)[r];
      for (uint64_t j = 0; u && j < segs[s].sub; ++j, dst += u)
        memcpy(dst, src + j * segs[s].sub_stride, u);
    }
    hb[r] = hash_bytes(rows + koff[r], koff[r + 1] - koff[r]);
  }
  auto width = [&](int32_t r) { return koff[r + 1] - koff[r]; };

  std::vector<int32_t> c_rows, c_verdict, c_firing, miss;
  miss.reserve(count);
  if (cache != nullptr) {
    std::lock_guard<std::mutex> lk(cache->mu);
    uint64_t hits = 0, misses = 0;
    for (int32_t r = 0; r < count; ++r) {
      if (eligible[r]) {
        int32_t i = cache->find(tokens[r], mix_token(hb[r], tokens[r]),
                                rows + koff[r], width(r));
        if (i >= 0) {
          cache->touch(i);
          ++hits;
          c_rows.push_back(r);
          c_verdict.push_back(cache->e[i].verdict);
          c_firing.push_back(cache->e[i].firing);
          continue;
        }
        ++misses;
      }
      miss.push_back(r);
    }
    Cache::bump(cache->hits, hits);
    Cache::bump(cache->misses, misses);
    p.elig_miss = (int32_t)misses;
  } else {
    for (int32_t r = 0; r < count; ++r) miss.push_back(r);
  }

  const size_t nm = miss.size();
  std::vector<int32_t> unique, inverse(nm);
  if (dedup) {
    // first occurrence wins, unique rows keep submission order (dedup_rows)
    size_t cap = 16;
    while (cap < nm * 2) cap <<= 1;
    std::vector<int32_t> table(cap, -1);
    for (size_t j = 0; j < nm; ++j) {
      const int32_t r = miss[j];
      size_t at = hb[r] & (cap - 1);
      for (;; at = (at + 1) & (cap - 1)) {
        const int32_t u = table[at];
        if (u < 0) {
          table[at] = (int32_t)unique.size();
          inverse[j] = (int32_t)unique.size();
          unique.push_back(r);
          break;
        }
        const int32_t ur = unique[u];
        if (hb[ur] == hb[r] && width(ur) == width(r) &&
            memcmp(rows + koff[ur], rows + koff[r], width(r)) == 0) {
          inverse[j] = u;
          break;
        }
      }
    }
  } else {
    unique = miss;
    for (size_t j = 0; j < nm; ++j) inverse[j] = (int32_t)j;
  }

  t.count = count;
  if (cache != nullptr) {
    t.at.push_back(0);
    for (int32_t r : unique) {
      if (!eligible[r]) continue;
      t.keys.insert(t.keys.end(), rows + koff[r], rows + koff[r + 1]);
      t.at.push_back(t.keys.size());
      t.tokens.push_back(tokens[r]);
      t.hashes.push_back(mix_token(hb[r], tokens[r]));
      t.rows.push_back(r);
    }
  }

  p.n_cached = (int32_t)c_rows.size();
  p.n_miss = (int32_t)nm;
  p.n_unique = (int32_t)unique.size();
  p.out.reserve(3 * c_rows.size() + 2 * nm + unique.size());
  for (const auto* v : {&c_rows, &c_verdict, &c_firing, &miss, &unique, &inverse})
    p.out.insert(p.out.end(), v->begin(), v->end());
}

// one launch's readback: rows [0, n) of a [>= n, W] uint8 array whose row
// r, byte j lies at packed + r * row_stride + j * col_stride (the runtime
// may hand back a row-major array with padded rows), bit 0 of a row = its
// verdict, bits 1..E its rule results, E+1..2E their skips
// (ops/pattern_eval.py unpack_attribution); put back at at[i] among the
// cut's launched rows (at nullptr: at i; at_wide: int64 positions, else
// int32)
struct Part {
  const uint8_t* packed;
  int64_t row_stride, col_stride, n, E;
  const void* at;
  bool at_wide;
};

// a CutPlan's arrays (authorino_tpu/native/verdict_cache.py), all int32
struct Fan {
  const int32_t *cached_rows, *cached_verdict, *cached_firing, *miss_rows, *inverse;
  int64_t n_cached, n_miss;
};

// firing_columns of one packed row, its bytes `stride` apart: the first of
// its E rule columns that evaluated false and was not skipped, else -1
inline int32_t first_firing(const uint8_t* row, int64_t stride, int64_t E) {
  auto bit = [=](int64_t k) { return (row[(k >> 3) * stride] >> (k & 7)) & 1; };
  for (int64_t j = 0; j < E; ++j)
    if (!bit(1 + j) && !bit(1 + E + j)) return (int32_t)j;
  return -1;
}

// a completed cut's verdicts (and firing columns, firing nullptr: none)
// into verdict[count] / firing[count]: each part decoded and put back among
// the `launched` rows, which `fan` (nullptr: the launched rows are the
// cut's) spreads over its miss rows; the cached rows take the plan's values.
// A row no part or plan reaches reads 0 / -1.  The caller has checked every
// position and row against its bound.
inline void resolve(const Part* parts, size_t nparts, int64_t launched, const Fan* fan,
                    int64_t count, uint8_t* verdict, int32_t* firing) {
  static thread_local std::vector<uint8_t> uv;
  static thread_local std::vector<int32_t> uf;
  uint8_t* lv = verdict;
  int32_t* lf = firing;
  if (fan != nullptr) {
    uv.assign((size_t)launched, 0);
    uf.assign(firing != nullptr ? (size_t)launched : 0, -1);
    lv = uv.data();
    lf = firing != nullptr ? uf.data() : nullptr;
  }
  memset(verdict, 0, (size_t)count);
  if (firing != nullptr) std::fill(firing, firing + count, -1);
  for (size_t p = 0; p < nparts; ++p) {
    const Part& q = parts[p];
    for (int64_t i = 0; i < q.n; ++i) {
      const uint8_t* row = q.packed + i * q.row_stride;
      const int64_t at = q.at == nullptr ? i
                         : q.at_wide     ? ((const int64_t*)q.at)[i]
                                         : ((const int32_t*)q.at)[i];
      lv[at] = row[0] & 1;
      if (lf != nullptr) lf[at] = first_firing(row, q.col_stride, q.E);
    }
  }
  if (fan == nullptr) return;
  for (int64_t j = 0; j < fan->n_miss; ++j) {
    verdict[fan->miss_rows[j]] = lv[fan->inverse[j]];
    if (firing != nullptr) firing[fan->miss_rows[j]] = lf[fan->inverse[j]];
  }
  // a cache hit attributes as the evaluation it memoized
  for (int64_t k = 0; k < fan->n_cached; ++k) {
    verdict[fan->cached_rows[k]] = (uint8_t)fan->cached_verdict[k];
    if (firing != nullptr) firing[fan->cached_rows[k]] = fan->cached_firing[k];
  }
}

// inserts the ticket's keys in order, each with the verdict (and firing
// column, -1 without attribution) of its row; returns the evictions it made.
// The ticket is spent: a second commit inserts nothing.
inline int64_t commit(Cache* cache, Ticket& t, const uint8_t* verdict, const int32_t* firing) {
  int64_t evicted = 0;
  {
    std::lock_guard<std::mutex> lk(cache->mu);
    for (size_t i = 0; i < t.rows.size(); ++i) {
      const int32_t r = t.rows[i];
      evicted += cache->put(t.tokens[i], t.hashes[i], t.keys.data() + t.at[i],
                            t.at[i + 1] - t.at[i], verdict[r],
                            firing != nullptr ? firing[r] : -1);
    }
  }
  t.rows.clear();
  return evicted;
}

}  // namespace vc
