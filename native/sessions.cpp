// Native session-metadata plane: ONE C sweep per batch for the host half
// of session windows (sessionize -> absorb -> slot-fold -> pop).
//
// This is the metadata sibling of native/slotmap.cpp: where the slotmap
// plays the RocksDB/ForSt batch-lookup role for the *state* plane, this
// table owns the *merge metadata* (reference: MergingWindowSet) — per-key
// live session intervals, the session-id allocator's fast path, and the
// lazy fire-candidate heap. The Python plane
// (flink_tpu/windowing/session_meta.py) remains the bit-identical
// fallback; flink_tpu/windowing/session_native.py is the ctypes wrapper.
//
// Layout:
//   - singles store: open-addressing hash key -> row over parallel
//     columns (key, start, end, sid, dslot, used). ``dslot`` FOLDS the
//     session's device-plane slot into the metadata row — engines verify
//     it against the state table's metadata views instead of re-probing
//     the state hash per batch (stale folds are harmless: verification
//     fails and the caller falls back to the probe).
//   - multi-key membership set: keys holding >= 2 live sessions live in
//     Python interval lists (exact reference semantics); this set only
//     answers "is this key multi?" during the sweep.
//   - fire chunks: columnar (ends, keys, sids) candidate chunks with
//     cached [lo, hi] end bounds — the watermark cut pops whole chunks
//     and splits only straddlers, exactly mirroring the Python plane's
//     chunk discipline (bit-identical pop order).
//
// All scalar run state (next_sid, max_fired_watermark) stays in Python —
// the sweep takes them as arguments, so there is exactly one source of
// truth and snapshots never consult this object.
//
// Exposed as a plain C ABI for ctypes; batch arguments are raw pointers
// into NumPy buffers.

#include <algorithm>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <vector>

namespace {

inline uint64_t mix_hash(uint64_t k) {
  uint64_t x = k ^ 0x9E3779B97F4A7C15ull;
  x ^= x >> 30;
  x *= 0xBF58476D1CE4E5B9ull;
  x ^= x >> 27;
  x *= 0x94D049BB133111EBull;
  x ^= x >> 31;
  return x;
}

constexpr int64_t kMinPendingEmpty = (int64_t)1 << 62;
constexpr int64_t kNegInf = -((int64_t)1 << 62);

struct Chunk {
  std::vector<int64_t> ends, keys, sids;
  // the session's metadata row at push time (-1 unknown): lets the pop
  // validate by direct row access instead of a hash probe — a stale
  // row (freed/reused since the push) falls back to the probe
  std::vector<int32_t> rows;
  int64_t lo = 0, hi = 0;
};

// one bucket of the grouped pass's batch-local table (16 bytes)
struct GroupEntry {
  int64_t key;
  uint32_t stamp;  // the batch that wrote it; any other reads as empty
  int32_t sess;    // the key's newest local session
};

struct SessionSet {
  // ------------------------------------------------------- singles store
  int64_t capacity = 0;      // row capacity (row 0 is a normal row here)
  int64_t max_capacity = 0;
  int64_t used = 0;
  int64_t bucket_count = 0;
  int32_t* buckets = nullptr;  // row id, -1 empty (backward-shift erase)
  int64_t* row_key = nullptr;
  int64_t* row_start = nullptr;
  int64_t* row_end = nullptr;
  int64_t* row_sid = nullptr;
  int32_t* row_dslot = nullptr;  // folded device slot, -1 unknown
  uint8_t* row_used = nullptr;
  int32_t* free_stack = nullptr;
  int64_t free_top = 0;
  // --------------------------------------------------- multi-key set
  int64_t multi_count = 0;
  uint64_t multi_buckets = 0;  // power of two
  int64_t* multi_key = nullptr;
  uint8_t* multi_used = nullptr;
  // --------------------------------------------------- fire candidates
  std::vector<Chunk*> chunks;
  int64_t min_pending = kMinPendingEmpty;
  // --------------------------------------------------------- pop scratch
  std::vector<int64_t> pk, ps, pe, psid;
  std::vector<int32_t> pslot;
  std::vector<int64_t> rk, rsid, re;
  // ------------------------------------------------------ sort scratch
  std::vector<uint64_t> sv0, sv1;
  std::vector<int64_t> si0, si1;
  std::vector<int64_t> fa_e, fa_k, fa_s, fb_e, fb_k, fb_s;
  std::vector<int32_t> fa_r, fb_r;
  // --------------------------------------------- grouped-pass scratch
  // batch-local key -> the key's current local session; an entry is
  // live only while its stamp is the batch's, so the table is never
  // cleared and keeps its size from batch to batch
  std::vector<GroupEntry> g_tab;
  uint32_t g_stamp = 0;
  // local sessions in the order they opened: key, first ts, last ts
  std::vector<int64_t> l_key, l_start, l_last;
  std::vector<uint64_t> gv0, gv1;   // rank: biased keys
  std::vector<int32_t> gi0, gi1;    // rank: local session numbers
  std::vector<int32_t> l_rank;      // local session -> its rank
};

// ------------------------------------------------------------- row hash

void build_buckets(SessionSet* m) {
  int64_t want = m->capacity * 2;
  int64_t bc = 64;
  while (bc < want) bc <<= 1;
  m->bucket_count = bc;
  free(m->buckets);
  m->buckets = (int32_t*)malloc(sizeof(int32_t) * bc);
  for (int64_t i = 0; i < bc; i++) m->buckets[i] = -1;
  uint64_t mask = (uint64_t)bc - 1;
  for (int64_t r = 0; r < m->capacity; r++) {
    if (!m->row_used[r]) continue;
    uint64_t i = mix_hash((uint64_t)m->row_key[r]) & mask;
    while (m->buckets[i] >= 0) i = (i + 1) & mask;
    m->buckets[i] = (int32_t)r;
  }
}

int grow(SessionSet* m) {
  if (m->capacity >= m->max_capacity) return -1;
  int64_t old_cap = m->capacity;
  int64_t new_cap = old_cap * 2;
  if (new_cap > m->max_capacity) new_cap = m->max_capacity;
  m->row_key = (int64_t*)realloc(m->row_key, sizeof(int64_t) * new_cap);
  m->row_start = (int64_t*)realloc(m->row_start, sizeof(int64_t) * new_cap);
  m->row_end = (int64_t*)realloc(m->row_end, sizeof(int64_t) * new_cap);
  m->row_sid = (int64_t*)realloc(m->row_sid, sizeof(int64_t) * new_cap);
  m->row_dslot = (int32_t*)realloc(m->row_dslot, sizeof(int32_t) * new_cap);
  m->row_used = (uint8_t*)realloc(m->row_used, new_cap);
  m->free_stack = (int32_t*)realloc(m->free_stack,
                                    sizeof(int32_t) * new_cap);
  memset(m->row_used + old_cap, 0, (size_t)(new_cap - old_cap));
  for (int64_t r = new_cap - 1; r >= old_cap; r--)
    m->free_stack[m->free_top++] = (int32_t)r;
  m->capacity = new_cap;
  build_buckets(m);
  return 0;
}

inline int32_t find_row(const SessionSet* m, int64_t key) {
  uint64_t mask = (uint64_t)m->bucket_count - 1;
  uint64_t i = mix_hash((uint64_t)key) & mask;
  for (;;) {
    int32_t b = m->buckets[i];
    if (b == -1) return -1;
    if (m->row_key[b] == key) return b;
    i = (i + 1) & mask;
  }
}

// returns the row, or -1 when the table is full at max capacity
inline int32_t insert_row(SessionSet* m, int64_t key) {
  uint64_t mask = (uint64_t)m->bucket_count - 1;
  uint64_t i = mix_hash((uint64_t)key) & mask;
  for (;;) {
    int32_t b = m->buckets[i];
    if (b == -1) {
      if (m->free_top == 0) {
        if (grow(m) != 0) return -1;
        mask = (uint64_t)m->bucket_count - 1;
        i = mix_hash((uint64_t)key) & mask;
        continue;
      }
      int32_t row = m->free_stack[--m->free_top];
      m->buckets[i] = row;
      m->row_key[row] = key;
      m->row_used[row] = 1;
      m->row_dslot[row] = -1;
      m->used++;
      return row;
    }
    if (m->row_key[b] == key) return b;
    i = (i + 1) & mask;
  }
}

// backward-shift erase (Knuth 6.4 R) — no tombstones under the heavy
// insert/erase churn of session fires
void erase_row(SessionSet* m, int32_t row) {
  uint64_t mask = (uint64_t)m->bucket_count - 1;
  uint64_t i = mix_hash((uint64_t)m->row_key[row]) & mask;
  while (m->buckets[i] != row) i = (i + 1) & mask;
  m->row_used[row] = 0;
  m->free_stack[m->free_top++] = row;
  m->used--;
  uint64_t hole = i;
  uint64_t j = (i + 1) & mask;
  while (m->buckets[j] != -1) {
    int32_t c = m->buckets[j];
    uint64_t home = mix_hash((uint64_t)m->row_key[c]) & mask;
    uint64_t dist_home = (j - home) & mask;
    uint64_t dist_hole = (j - hole) & mask;
    if (dist_home >= dist_hole) {
      m->buckets[hole] = c;
      hole = j;
    }
    j = (j + 1) & mask;
  }
  m->buckets[hole] = -1;
}

// --------------------------------------------------------- multi-key set

void multi_rebuild(SessionSet* m, uint64_t nb) {
  int64_t* ok = m->multi_key;
  uint8_t* ou = m->multi_used;
  uint64_t onb = m->multi_buckets;
  m->multi_key = (int64_t*)malloc(sizeof(int64_t) * nb);
  m->multi_used = (uint8_t*)calloc(nb, 1);
  m->multi_buckets = nb;
  if (ok) {
    for (uint64_t i = 0; i < onb; i++) {
      if (!ou[i]) continue;
      uint64_t j = mix_hash((uint64_t)ok[i]) & (nb - 1);
      while (m->multi_used[j]) j = (j + 1) & (nb - 1);
      m->multi_key[j] = ok[i];
      m->multi_used[j] = 1;
    }
  }
  free(ok);
  free(ou);
}

inline bool multi_contains(const SessionSet* m, int64_t key) {
  if (m->multi_count == 0) return false;
  uint64_t mask = m->multi_buckets - 1;
  uint64_t i = mix_hash((uint64_t)key) & mask;
  while (m->multi_used[i]) {
    if (m->multi_key[i] == key) return true;
    i = (i + 1) & mask;
  }
  return false;
}

// ------------------------------------------------------------ fire chunks

void push_chunk(SessionSet* m, const int64_t* ends, const int64_t* keys,
                const int64_t* sids, const int32_t* rows, int64_t n) {
  if (n == 0) return;
  Chunk* c = new Chunk();
  c->ends.assign(ends, ends + n);
  c->keys.assign(keys, keys + n);
  c->sids.assign(sids, sids + n);
  if (rows != nullptr) {
    c->rows.assign(rows, rows + n);
  } else {
    c->rows.assign(n, -1);
  }
  int64_t lo = ends[0], hi = ends[0];
  for (int64_t i = 1; i < n; i++) {
    if (ends[i] < lo) lo = ends[i];
    if (ends[i] > hi) hi = ends[i];
  }
  c->lo = lo;
  c->hi = hi;
  m->chunks.push_back(c);
  if (lo < m->min_pending) m->min_pending = lo;
}

// ------------------------------------------------- stable radix argsort

// LSD radix argsort over biased-unsigned 64-bit values; stable, so it
// reproduces numpy's kind="stable" permutation exactly. vals is
// clobbered; idx receives the order.
void radix_argsort(SessionSet* m, std::vector<uint64_t>& vals,
                   std::vector<int64_t>& idx, int64_t n) {
  m->sv1.resize(n);
  m->si1.resize(n);
  uint64_t maxv = 0;
  for (int64_t i = 0; i < n; i++)
    if (vals[i] > maxv) maxv = vals[i];
  static thread_local std::vector<int64_t> count;
  count.resize(1 << 16);
  uint64_t* a = vals.data();
  uint64_t* b = m->sv1.data();
  int64_t* ia = idx.data();
  int64_t* ib = m->si1.data();
  for (int pass = 0; pass < 4; pass++) {
    int shift = pass * 16;
    if (pass > 0 && (maxv >> shift) == 0) break;  // higher digits all 0
    std::fill(count.begin(), count.end(), 0);
    for (int64_t i = 0; i < n; i++) count[(a[i] >> shift) & 0xffff]++;
    if (count[(a[0] >> shift) & 0xffff] == n) continue;  // constant digit
    int64_t total = 0;
    for (int64_t d = 0; d < (1 << 16); d++) {
      int64_t c = count[d];
      count[d] = total;
      total += c;
    }
    for (int64_t i = 0; i < n; i++) {
      int64_t pos = count[(a[i] >> shift) & 0xffff]++;
      b[pos] = a[i];
      ib[pos] = ia[i];
    }
    std::swap(a, b);
    std::swap(ia, ib);
  }
  if (ia != idx.data()) {
    memcpy(idx.data(), ia, sizeof(int64_t) * n);
  }
}

// stable (key, ts) argsort — identical permutation to the Python
// plane's packed argsort / lexsort (both stable over the same ordering)
void sort_order(SessionSet* m, const int64_t* keys, const int64_t* ts,
                int64_t n, int64_t* order) {
  int64_t tmin = ts[0], tmax = ts[0], kmin = keys[0], kmax = keys[0];
  for (int64_t i = 1; i < n; i++) {
    if (ts[i] < tmin) tmin = ts[i];
    if (ts[i] > tmax) tmax = ts[i];
    if (keys[i] < kmin) kmin = keys[i];
    if (keys[i] > kmax) kmax = keys[i];
  }
  uint64_t span = (uint64_t)(tmax - tmin);
  int shift = 1;
  while (shift < 64 && (span >> shift) != 0) shift++;
  bool packable = shift <= 62 && kmin >= 0 &&
                  ((uint64_t)kmax >> (62 - shift)) == 0;
  if (packable) {
    m->sv0.resize(n);
    m->si0.resize(n);
    for (int64_t i = 0; i < n; i++) {
      m->sv0[i] = ((uint64_t)keys[i] << shift) | (uint64_t)(ts[i] - tmin);
      m->si0[i] = i;
    }
    radix_argsort(m, m->sv0, m->si0, n);
    memcpy(order, m->si0.data(), sizeof(int64_t) * n);
  } else {
    for (int64_t i = 0; i < n; i++) order[i] = i;
    std::stable_sort(order, order + n, [&](int64_t x, int64_t y) {
      if (keys[x] != keys[y]) return keys[x] < keys[y];
      return ts[x] < ts[y];
    });
  }
}

// ------------------------------------------------- sessionize one batch
//
// Two ways to the same arrays. Both yield the batch-local sessions in
// ascending (key, start) order — sess_key / sess_start / sess_end — and
// rec_sess[i], the session of record i.

// SORTED: stable (key, ts) argsort, then a gap scan over the sorted
// stream. Takes any batch; also yields the permutation itself (order)
// and its session column (rec_to_sess).
int64_t sessionize_sorted(SessionSet* m, const int64_t* keys,
                          const int64_t* ts, int64_t n, int64_t gap,
                          int64_t* order, int64_t* rec_to_sess,
                          int32_t* rec_sess, int64_t* sess_key,
                          int64_t* sess_start, int64_t* sess_end) {
  sort_order(m, keys, ts, n, order);
  int64_t ms = 0;
  int64_t prev_key = 0, prev_ts = 0;
  for (int64_t i = 0; i < n; i++) {
    int64_t at = order[i];
    int64_t k = keys[at];
    int64_t t = ts[at];
    if (i == 0 || k != prev_key || t - prev_ts > gap) {
      sess_key[ms] = k;
      sess_start[ms] = t;
      ms++;
    }
    sess_end[ms - 1] = t + gap;
    rec_to_sess[i] = ms - 1;
    rec_sess[at] = (int32_t)(ms - 1);
    prev_key = k;
    prev_ts = t;
  }
  return ms;
}

inline uint64_t group_home(int64_t key, int shift) {
  // Fibonacci hashing: the product's top bits
  return ((uint64_t)key * 0x9E3779B97F4A7C15ull) >> shift;
}

// fourfold, as the slot map's tables grow; the batch's live entries
// move over, everything else is left behind
void group_grow(SessionSet* m) {
  std::vector<GroupEntry> old;
  old.swap(m->g_tab);
  m->g_tab.assign(old.size() * 4, GroupEntry{0, 0, 0});
  uint64_t mask = m->g_tab.size() - 1;
  int shift = 64 - __builtin_ctzll(m->g_tab.size());
  for (const GroupEntry& e : old) {
    if (e.stamp != m->g_stamp) continue;
    uint64_t b = group_home(e.key, shift);
    while (m->g_tab[b].stamp == m->g_stamp) b = (b + 1) & mask;
    m->g_tab[b] = e;
  }
}

// Stable rank of the local sessions by key (LSD radix over the keys
// less their minimum, digits of at most 12 bits). A key's sessions
// opened in ascending start order, so stable by key IS (key, start).
// Writes the session arrays in rank order and l_rank[local] = rank.
void rank_local_sessions(SessionSet* m, int64_t ml, int64_t gap,
                         int64_t* sess_key, int64_t* sess_start,
                         int64_t* sess_end) {
  const int64_t* lk = m->l_key.data();
  int64_t kmin = lk[0], kmax = lk[0];
  bool ascending = true;
  for (int64_t j = 1; j < ml; j++) {
    if (lk[j] < lk[j - 1]) ascending = false;
    if (lk[j] < kmin) kmin = lk[j];
    if (lk[j] > kmax) kmax = lk[j];
  }
  m->l_rank.resize(ml);
  m->gi0.resize(ml);
  int32_t* idx = m->gi0.data();
  for (int64_t j = 0; j < ml; j++) idx[j] = (int32_t)j;
  if (!ascending) {
    uint64_t span = (uint64_t)kmax - (uint64_t)kmin;
    int bits = 64 - __builtin_clzll(span);  // span > 0 here
    int passes = (bits + 11) / 12;
    int digit = (bits + passes - 1) / passes;
    uint64_t dmask = ((uint64_t)1 << digit) - 1;
    m->gv0.resize(ml);
    m->gv1.resize(ml);
    m->gi1.resize(ml);
    uint64_t* a = m->gv0.data();
    uint64_t* b = m->gv1.data();
    int32_t* ib = m->gi1.data();
    for (int64_t j = 0; j < ml; j++) a[j] = (uint64_t)lk[j] - (uint64_t)kmin;
    int32_t count[1 << 12];
    for (int pass = 0; pass < passes; pass++) {
      int shift = pass * digit;
      memset(count, 0, sizeof(int32_t) << digit);
      for (int64_t j = 0; j < ml; j++) count[(a[j] >> shift) & dmask]++;
      int32_t total = 0;
      for (uint64_t d = 0; d <= dmask; d++) {
        int32_t c = count[d];
        count[d] = total;
        total += c;
      }
      for (int64_t j = 0; j < ml; j++) {
        int32_t pos = count[(a[j] >> shift) & dmask]++;
        b[pos] = a[j];
        ib[pos] = idx[j];
      }
      std::swap(a, b);
      std::swap(idx, ib);
    }
  }
  const int64_t* ls = m->l_start.data();
  const int64_t* ll = m->l_last.data();
  int32_t* rank = m->l_rank.data();
  for (int64_t r = 0; r < ml; r++) {
    int32_t j = idx[r];
    sess_key[r] = lk[j];
    sess_start[r] = ls[j];
    sess_end[r] = ll[j] + gap;
    rank[j] = (int32_t)r;
  }
}

// GROUPED: one pass over the batch in arrival order against a
// batch-local table key -> the key's newest local session. Valid while
// no key's timestamps step backwards inside the batch — then a key's
// records stand in stable (key, ts) order as they arrived, and the gap
// rule applied record by record opens exactly the sessions the sorted
// scan opens. The pass observes the precondition itself: the first
// record below its key's last one ends it with -1 and nothing written
// that the sorted form does not overwrite. Else the session count.
int64_t sessionize_grouped(SessionSet* m, const int64_t* keys,
                           const int64_t* ts, int64_t n, int64_t gap,
                           int32_t* rec_sess, int64_t* sess_key,
                           int64_t* sess_start, int64_t* sess_end) {
  if (n > INT32_MAX) return -1;
  if (m->g_tab.empty()) m->g_tab.assign(1024, GroupEntry{0, 0, 0});
  if (++m->g_stamp == 0) {  // the stamp came round: forget every entry
    for (GroupEntry& e : m->g_tab) e.stamp = 0;
    m->g_stamp = 1;
  }
  if ((int64_t)m->l_key.size() < n) {
    m->l_key.resize(n);
    m->l_start.resize(n);
    m->l_last.resize(n);
  }
  const uint32_t stamp = m->g_stamp;
  GroupEntry* tab = m->g_tab.data();
  uint64_t size = m->g_tab.size();
  uint64_t mask = size - 1;
  int shift = 64 - __builtin_ctzll(size);
  int64_t* lk = m->l_key.data();
  int64_t* ls = m->l_start.data();
  int64_t* ll = m->l_last.data();
  uint64_t n_keys = 0;
  int32_t ml = 0;
  for (int64_t i = 0; i < n; i++) {
    const int64_t k = keys[i];
    const int64_t t = ts[i];
    uint64_t b = group_home(k, shift);
    for (;;) {
      GroupEntry& e = tab[b];
      if (e.stamp != stamp) {  // a key the batch has not met
        if ((n_keys + 1) * 2 > size) {
          group_grow(m);
          tab = m->g_tab.data();
          size = m->g_tab.size();
          mask = size - 1;
          shift = 64 - __builtin_ctzll(size);
          b = group_home(k, shift);
          continue;
        }
        n_keys++;
        e.key = k;
        e.stamp = stamp;
      } else if (e.key != k) {
        b = (b + 1) & mask;
        continue;
      } else {
        const int32_t s = e.sess;
        const int64_t last = ll[s];
        if (t < last) return -1;  // a step backwards: sort instead
        if (t - last <= gap) {
          ll[s] = t;
          rec_sess[i] = s;
          break;
        }
      }
      // open the key's next local session
      e.sess = ml;
      lk[ml] = k;
      ls[ml] = t;
      ll[ml] = t;
      rec_sess[i] = ml;
      ml++;
      break;
    }
  }
  rank_local_sessions(m, ml, gap, sess_key, sess_start, sess_end);
  const int32_t* rank = m->l_rank.data();
  for (int64_t i = 0; i < n; i++) rec_sess[i] = rank[rec_sess[i]];
  return ml;
}

}  // namespace

extern "C" {

void* sx_create(int64_t initial_capacity, int64_t max_capacity) {
  if (initial_capacity < 1024) initial_capacity = 1024;
  if (max_capacity < initial_capacity) max_capacity = initial_capacity;
  SessionSet* m = new SessionSet();
  m->capacity = initial_capacity;
  m->max_capacity = max_capacity;
  m->row_key = (int64_t*)calloc(initial_capacity, sizeof(int64_t));
  m->row_start = (int64_t*)calloc(initial_capacity, sizeof(int64_t));
  m->row_end = (int64_t*)calloc(initial_capacity, sizeof(int64_t));
  m->row_sid = (int64_t*)calloc(initial_capacity, sizeof(int64_t));
  m->row_dslot = (int32_t*)malloc(sizeof(int32_t) * initial_capacity);
  for (int64_t i = 0; i < initial_capacity; i++) m->row_dslot[i] = -1;
  m->row_used = (uint8_t*)calloc(initial_capacity, 1);
  m->free_stack = (int32_t*)malloc(sizeof(int32_t) * initial_capacity);
  m->free_top = 0;
  for (int64_t r = initial_capacity - 1; r >= 0; r--)
    m->free_stack[m->free_top++] = (int32_t)r;
  build_buckets(m);
  multi_rebuild(m, 64);
  return m;
}

void sx_destroy(void* h) {
  SessionSet* m = (SessionSet*)h;
  free(m->buckets);
  free(m->row_key);
  free(m->row_start);
  free(m->row_end);
  free(m->row_sid);
  free(m->row_dslot);
  free(m->row_used);
  free(m->free_stack);
  free(m->multi_key);
  free(m->multi_used);
  for (Chunk* c : m->chunks) delete c;
  delete m;
}

int64_t sx_capacity(void* h) { return ((SessionSet*)h)->capacity; }
int64_t sx_used(void* h) { return ((SessionSet*)h)->used; }
const int64_t* sx_keys(void* h) { return ((SessionSet*)h)->row_key; }
int64_t* sx_starts(void* h) { return ((SessionSet*)h)->row_start; }
int64_t* sx_ends(void* h) { return ((SessionSet*)h)->row_end; }
int64_t* sx_sids(void* h) { return ((SessionSet*)h)->row_sid; }
int32_t* sx_dslots(void* h) { return ((SessionSet*)h)->row_dslot; }
const uint8_t* sx_used_mask(void* h) { return ((SessionSet*)h)->row_used; }

void sx_lookup(void* h, int64_t n, const int64_t* keys, int32_t* out_rows) {
  SessionSet* m = (SessionSet*)h;
  for (int64_t i = 0; i < n; i++) out_rows[i] = find_row(m, keys[i]);
}

// lookup-or-insert; new rows get dslot=-1 and zeroed interval columns
// (the Python caller writes start/end/sid through the views). Returns
// the number of grows (>0: caller re-wraps views), or -1 when full.
int32_t sx_insert(void* h, int64_t n, const int64_t* keys,
                  int32_t* out_rows) {
  SessionSet* m = (SessionSet*)h;
  int64_t cap0 = m->capacity;
  for (int64_t i = 0; i < n; i++) {
    int32_t r = insert_row(m, keys[i]);
    if (r < 0) return -1;
    out_rows[i] = r;
  }
  int32_t grows = 0;
  for (int64_t c = cap0; c < m->capacity; c *= 2) grows++;
  return grows;
}

void sx_erase_rows(void* h, int64_t n, const int32_t* rows) {
  SessionSet* m = (SessionSet*)h;
  for (int64_t i = 0; i < n; i++) {
    if (rows[i] >= 0 && m->row_used[rows[i]]) erase_row(m, rows[i]);
  }
}

// Scalar forms for the Python slow path (_merge_session walks one
// session at a time): plain int in / int out, no pointer marshalling —
// the array forms cost more in ctypes casts than in hashing at a
// batch of one.
int32_t sx_lookup1(void* h, int64_t key) {
  return find_row((SessionSet*)h, key);
}

int32_t sx_insert1(void* h, int64_t key) {
  return insert_row((SessionSet*)h, key);  // -1 when full at max cap
}

void sx_erase1(void* h, int32_t row) {
  SessionSet* m = (SessionSet*)h;
  if (row >= 0 && m->row_used[row]) erase_row(m, row);
}

void sx_multi_add(void* h, int64_t key) {
  SessionSet* m = (SessionSet*)h;
  if (multi_contains(m, key)) return;
  if ((uint64_t)(m->multi_count + 1) * 2 >= m->multi_buckets)
    multi_rebuild(m, m->multi_buckets * 2);
  uint64_t mask = m->multi_buckets - 1;
  uint64_t i = mix_hash((uint64_t)key) & mask;
  while (m->multi_used[i]) i = (i + 1) & mask;
  m->multi_key[i] = key;
  m->multi_used[i] = 1;
  m->multi_count++;
}

void sx_multi_remove(void* h, int64_t key) {
  SessionSet* m = (SessionSet*)h;
  if (m->multi_count == 0) return;
  uint64_t mask = m->multi_buckets - 1;
  uint64_t i = mix_hash((uint64_t)key) & mask;
  while (m->multi_used[i]) {
    if (m->multi_key[i] == key) {
      m->multi_used[i] = 0;
      m->multi_count--;
      // backward-shift compaction of the probe chain
      uint64_t hole = i;
      uint64_t j = (i + 1) & mask;
      while (m->multi_used[j]) {
        uint64_t home = mix_hash((uint64_t)m->multi_key[j]) & mask;
        uint64_t dist_home = (j - home) & mask;
        uint64_t dist_hole = (j - hole) & mask;
        if (dist_home >= dist_hole) {
          m->multi_key[hole] = m->multi_key[j];
          m->multi_used[hole] = 1;
          m->multi_used[j] = 0;
          hole = j;
        }
        j = (j + 1) & mask;
      }
      return;
    }
    i = (i + 1) & mask;
  }
}

int64_t sx_multi_count(void* h) { return ((SessionSet*)h)->multi_count; }

// Batched probe-and-set of the folded device slot: rows whose stored
// sid still matches take the new slot (a session that merged or fired
// between resolve and fold simply keeps its fold unset).
void sx_fold(void* h, int64_t n, const int64_t* keys, const int64_t* sids,
             const int32_t* slots) {
  SessionSet* m = (SessionSet*)h;
  for (int64_t i = 0; i < n; i++) {
    int32_t r = find_row(m, keys[i]);
    if (r >= 0 && m->row_sid[r] == sids[i]) m->row_dslot[r] = slots[i];
  }
}

// Row-addressed fold: the caller holds the sessions' metadata rows
// from this batch's sweep; the sid guard drops any row the slow path
// re-purposed between sweep and fold. One direct-indexed pass.
void sx_fold_rows(void* h, int64_t n, const int32_t* rows,
                  const int64_t* sids, const int32_t* slots) {
  SessionSet* m = (SessionSet*)h;
  constexpr int64_t CHUNK = 256;
  for (int64_t base = 0; base < n; base += CHUNK) {
    int64_t end = base + CHUNK < n ? base + CHUNK : n;
    for (int64_t i = base; i < end; i++) {
      if (rows[i] >= 0) __builtin_prefetch(&m->row_sid[rows[i]], 0, 1);
    }
    for (int64_t i = base; i < end; i++) {
      int32_t r = rows[i];
      if (r >= 0 && m->row_sid[r] == sids[i]) m->row_dslot[r] = slots[i];
    }
  }
}

void sx_push_chunk(void* h, int64_t n, const int64_t* ends,
                   const int64_t* keys, const int64_t* sids) {
  // Python-side pushes (slow-path buffer drains, restore) carry no row
  // knowledge — those candidates validate via the hash probe
  push_chunk((SessionSet*)h, ends, keys, sids, nullptr, n);
}

int64_t sx_min_pending(void* h) { return ((SessionSet*)h)->min_pending; }

// The fused absorb sweep — ONE foreign call per batch doing what the
// Python plane does in ~a dozen vectorized numpy passes:
//
//   1. sessionize: the batch-local sessions (key, min_ts, max_ts + gap)
//      in ascending (key, start) order and each record's session
//      (rec_sess, arrival order). GROUPED — one hash pass over the batch
//      as it arrived, then a rank of the sessions, not the records —
//      wherever no key's timestamps step backwards inside the batch,
//      which the pass itself observes (sessionize_grouped);
//   2. else SORTED, as the Python plane does it: stable (key, ts)
//      argsort (radix when the span packs, mirroring the Python
//      packed-argsort condition — the permutation is identical either
//      way) and a gap scan over the sorted stream. Only this form fills
//      ``order`` / ``rec_to_sess``; after a grouped batch they follow
//      from rec_sess by one counting pass (sx_sorted_maps), bit for bit
//      the sort's, for whoever asks. ``out[OUT_GROUPED]`` says which ran;
//   3. classify + apply per session, ascending:
//        FRESH    sole local session, key unknown, not stale: insert a
//                 store row, allocate sid (contiguous block from
//                 ``next_sid``, matching the Python fast path), queue
//                 its fire candidate;
//        EXTENDED sole local session overlapping the key's stored
//                 single: min/max-extend in place, expose the stored
//                 sid AND the folded device slot, queue a fire
//                 candidate iff the end changed;
//        STALE    fresh but already behind the fired watermark
//                 (sid = -1, never stored);
//        SLOW     everything multi-flavored or disjoint-second — the
//                 Python caller runs the exact reference-shaped merge.
//
// Fire candidates land as two chunks (FRESH then EXTENDED) in exactly
// the Python plane's push order, so pop order stays bit-identical.
// Returns the session count m, or -1 when the store hit max capacity.
// ``out`` takes the scalars the caller would else count over the flag
// column or ask for in a call of its own (each of those is a GIL
// hand-over on the task loop): sids allocated, whether the batch was
// grouped, SLOW and STALE sessions, the store's row capacity after.
enum { OUT_N_FAST, OUT_GROUPED, OUT_N_SLOW, OUT_N_STALE, OUT_CAPACITY };

static int64_t absorb(SessionSet* m, bool try_grouped, int64_t n,
                      const int64_t* keys, const int64_t* ts, int64_t gap,
                      int64_t lateness, int64_t max_fired_wm,
                      int64_t next_sid, int64_t* order,
                      int64_t* rec_to_sess, int32_t* rec_sess,
                      int64_t* sess_key, int64_t* sess_start,
                      int64_t* sess_end, int64_t* sess_sid,
                      int32_t* sess_slot, int32_t* sess_row,
                      uint8_t* sess_flag, int64_t* out) {
  out[OUT_N_FAST] = out[OUT_GROUPED] = out[OUT_N_SLOW] = 0;
  out[OUT_N_STALE] = 0;
  out[OUT_CAPACITY] = m->capacity;
  if (n == 0) return 0;
  int64_t ms = -1;
  if (try_grouped) {
    ms = sessionize_grouped(m, keys, ts, n, gap, rec_sess, sess_key,
                            sess_start, sess_end);
  }
  if (ms >= 0) {
    out[OUT_GROUPED] = 1;
  } else {
    ms = sessionize_sorted(m, keys, ts, n, gap, order, rec_to_sess,
                           rec_sess, sess_key, sess_start, sess_end);
  }
  const bool have_wm = max_fired_wm > kNegInf / 2;
  m->fa_e.clear(); m->fa_k.clear(); m->fa_s.clear(); m->fa_r.clear();
  m->fb_e.clear(); m->fb_k.clear(); m->fb_s.clear(); m->fb_r.clear();
  int64_t n_fast = 0, n_slow = 0, n_stale = 0;
  // chunked software prefetch (the slotmap discipline): the store spans
  // far more than L2 at high cardinality, so the bucket probe and the
  // row verify are each a likely miss. Hash a chunk of session keys up
  // front, prefetch their home buckets, then peek the (warm) buckets to
  // prefetch the row columns. Inserts during processing only make
  // hints stale — never wrong.
  constexpr int64_t CHUNK = 256;
  uint64_t hashes[CHUNK];
  for (int64_t base = 0; base < ms; base += CHUNK) {
    int64_t endj = base + CHUNK < ms ? base + CHUNK : ms;
    uint64_t pmask = (uint64_t)m->bucket_count - 1;
    for (int64_t j = base; j < endj; j++) {
      uint64_t hh = mix_hash((uint64_t)sess_key[j]);
      hashes[j - base] = hh;
      __builtin_prefetch(&m->buckets[hh & pmask], 0, 1);
    }
    int64_t miss_guess = 0;
    for (int64_t j = base; j < endj; j++) {
      int32_t b = m->buckets[hashes[j - base] & pmask];
      if (b >= 0) {
        __builtin_prefetch(&m->row_key[b], 0, 1);
        __builtin_prefetch(&m->row_end[b], 0, 1);
      } else if (m->free_top > miss_guess) {
        // empty home bucket -> this key likely INSERTS; the free
        // stack is LIFO, so the miss_guess-th miss of this chunk will
        // take free_stack[top-1-miss_guess] — prefetch its row
        // columns for write (a wrong guess only wastes the hint)
        int32_t r = m->free_stack[m->free_top - 1 - miss_guess];
        miss_guess++;
        __builtin_prefetch(&m->row_key[r], 1, 1);
        __builtin_prefetch(&m->row_start[r], 1, 1);
        __builtin_prefetch(&m->row_end[r], 1, 1);
        __builtin_prefetch(&m->row_sid[r], 1, 1);
        __builtin_prefetch(&m->row_dslot[r], 1, 1);
      }
    }
  for (int64_t j = base; j < endj; j++) {
    int64_t k = sess_key[j];
    bool first = (j == 0) || sess_key[j - 1] != k;
    bool only = first && (j == ms - 1 || sess_key[j + 1] != k);
    sess_slot[j] = -1;
    sess_row[j] = -1;
    if (only) {
      int32_t row = find_row(m, k);
      if (row >= 0) {
        int64_t ex_s = m->row_start[row], ex_e = m->row_end[row];
        if (sess_start[j] <= ex_e && ex_s <= sess_end[j]) {
          // overlap-extend the stored single in place
          int64_t ns_ = ex_s < sess_start[j] ? ex_s : sess_start[j];
          int64_t ne_ = ex_e > sess_end[j] ? ex_e : sess_end[j];
          bool changed = ne_ != ex_e;
          m->row_start[row] = ns_;
          m->row_end[row] = ne_;
          sess_sid[j] = m->row_sid[row];
          sess_slot[j] = m->row_dslot[row];
          sess_row[j] = row;
          sess_flag[j] = 1;  // EXTENDED
          if (changed) {
            m->fb_e.push_back(ne_);
            m->fb_k.push_back(k);
            m->fb_s.push_back(m->row_sid[row]);
            m->fb_r.push_back(row);
          }
          continue;
        }
        sess_flag[j] = 2;  // SLOW: disjoint second session of the key
        sess_sid[j] = 0;
        n_slow++;
        continue;
      }
      if (!multi_contains(m, k)) {
        if (have_wm && sess_end[j] - 1 + lateness <= max_fired_wm) {
          sess_flag[j] = 3;  // STALE on arrival (never stored)
          sess_sid[j] = -1;
          n_stale++;
          continue;
        }
        int64_t sid = next_sid + n_fast;
        n_fast++;
        int32_t r = insert_row(m, k);
        if (r < 0) return -1;
        m->row_start[r] = sess_start[j];
        m->row_end[r] = sess_end[j];
        m->row_sid[r] = sid;
        m->row_dslot[r] = -1;
        sess_sid[j] = sid;
        sess_row[j] = r;
        sess_flag[j] = 0;  // FRESH
        m->fa_e.push_back(sess_end[j]);
        m->fa_k.push_back(k);
        m->fa_s.push_back(sid);
        m->fa_r.push_back(r);
        continue;
      }
    }
    sess_flag[j] = 2;  // SLOW: the Python merge path fills the sid
    sess_sid[j] = 0;
    n_slow++;
  }
  }
  // fire-candidate chunks in the Python plane's push order: the FRESH
  // block first, then the EXTENDED block
  push_chunk(m, m->fa_e.data(), m->fa_k.data(), m->fa_s.data(),
             m->fa_r.data(), (int64_t)m->fa_e.size());
  push_chunk(m, m->fb_e.data(), m->fb_k.data(), m->fb_s.data(),
             m->fb_r.data(), (int64_t)m->fb_e.size());
  out[OUT_N_FAST] = n_fast;
  out[OUT_N_SLOW] = n_slow;
  out[OUT_N_STALE] = n_stale;
  out[OUT_CAPACITY] = m->capacity;
  return ms;
}

int64_t sx_absorb(void* h, int64_t n, const int64_t* keys, const int64_t* ts,
                  int64_t gap, int64_t lateness, int64_t max_fired_wm,
                  int64_t next_sid, int64_t* order, int64_t* rec_to_sess,
                  int32_t* rec_sess, int64_t* sess_key, int64_t* sess_start,
                  int64_t* sess_end, int64_t* sess_sid, int32_t* sess_slot,
                  int32_t* sess_row, uint8_t* sess_flag, int64_t* out) {
  return absorb((SessionSet*)h, true, n, keys, ts, gap, lateness,
                max_fired_wm, next_sid, order, rec_to_sess, rec_sess,
                sess_key, sess_start, sess_end, sess_sid, sess_slot,
                sess_row, sess_flag, out);
}

// The same sweep held to the sorted form whatever the batch: what the
// parity tests compare the grouped pass with. Nothing else calls it.
int64_t sx_absorb_sorted(void* h, int64_t n, const int64_t* keys,
                         const int64_t* ts, int64_t gap, int64_t lateness,
                         int64_t max_fired_wm, int64_t next_sid,
                         int64_t* order, int64_t* rec_to_sess,
                         int32_t* rec_sess, int64_t* sess_key,
                         int64_t* sess_start, int64_t* sess_end,
                         int64_t* sess_sid, int32_t* sess_slot,
                         int32_t* sess_row, uint8_t* sess_flag,
                         int64_t* out) {
  return absorb((SessionSet*)h, false, n, keys, ts, gap, lateness,
                max_fired_wm, next_sid, order, rec_to_sess, rec_sess,
                sess_key, sess_start, sess_end, sess_sid, sess_slot,
                sess_row, sess_flag, out);
}

// ``order`` / ``rec_to_sess`` of a grouped batch, from rec_sess alone:
// the sessions stand in (key, start) order and a key's records arrived
// in timestamp order, so a record's place in the stable (key, ts)
// permutation is its session's first place plus the records of that
// session before it — one counting pass.
void sx_sorted_maps(int64_t n, int64_t m, const int32_t* rec_sess,
                    int64_t* order, int64_t* rec_to_sess) {
  static thread_local std::vector<int64_t> cursor;
  cursor.assign(m + 1, 0);
  for (int64_t i = 0; i < n; i++) cursor[rec_sess[i] + 1]++;
  for (int64_t j = 0; j < m; j++) cursor[j + 1] += cursor[j];
  for (int64_t i = 0; i < n; i++) {
    int64_t j = rec_sess[i];
    int64_t pos = cursor[j]++;
    order[pos] = i;
    rec_to_sess[pos] = j;
  }
}

// The chunk-bounded watermark cut + validate + remove, in one sweep:
// wholly-due chunks pop whole, wholly-pending chunks are untouched,
// straddlers split once. Due candidates stable-sort by end (the heap
// pop order), validate against the singles store (sid AND end must
// match — merged/extended sessions left stale candidates behind), and
// the fired rows leave the store with their (key, start, end, sid,
// folded slot) columns staged for fetch. Candidates whose key is not
// in the singles store at all are returned as the REST set for the
// Python multi-interval walk. Returns the fired-singles count.
int64_t sx_pop(void* h, int64_t watermark, int64_t* out_rest) {
  SessionSet* m = (SessionSet*)h;
  m->pk.clear(); m->ps.clear(); m->pe.clear(); m->psid.clear();
  m->pslot.clear();
  m->rk.clear(); m->rsid.clear(); m->re.clear();
  *out_rest = 0;
  std::vector<Chunk*> kept;
  static thread_local std::vector<int64_t> due_e, due_k, due_s;
  static thread_local std::vector<int32_t> due_r;
  due_e.clear(); due_k.clear(); due_s.clear(); due_r.clear();
  int64_t minp = kMinPendingEmpty;
  for (Chunk* c : m->chunks) {
    int64_t nc = (int64_t)c->ends.size();
    if (c->hi - 1 <= watermark) {  // wholly due
      due_e.insert(due_e.end(), c->ends.begin(), c->ends.end());
      due_k.insert(due_k.end(), c->keys.begin(), c->keys.end());
      due_s.insert(due_s.end(), c->sids.begin(), c->sids.end());
      due_r.insert(due_r.end(), c->rows.begin(), c->rows.end());
      delete c;
    } else if (c->lo - 1 > watermark) {  // wholly pending: untouched
      kept.push_back(c);
      if (c->lo < minp) minp = c->lo;
    } else {  // straddler: split once
      Chunk* k2 = new Chunk();
      int64_t lo = 0, hi = 0;
      bool any = false;
      for (int64_t i = 0; i < nc; i++) {
        if (c->ends[i] - 1 <= watermark) {
          due_e.push_back(c->ends[i]);
          due_k.push_back(c->keys[i]);
          due_s.push_back(c->sids[i]);
          due_r.push_back(c->rows[i]);
        } else {
          k2->ends.push_back(c->ends[i]);
          k2->keys.push_back(c->keys[i]);
          k2->sids.push_back(c->sids[i]);
          k2->rows.push_back(c->rows[i]);
          if (!any) {
            lo = hi = c->ends[i];
            any = true;
          } else {
            if (c->ends[i] < lo) lo = c->ends[i];
            if (c->ends[i] > hi) hi = c->ends[i];
          }
        }
      }
      delete c;
      k2->lo = lo;
      k2->hi = hi;
      kept.push_back(k2);
      if (lo < minp) minp = lo;
    }
  }
  m->chunks = kept;
  m->min_pending = minp;
  int64_t nd = (int64_t)due_e.size();
  if (nd == 0) return 0;
  // stable argsort by end; min-biased so the radix skips the dead
  // upper digit passes (watermark pops see a narrow end range)
  int64_t emin = due_e[0];
  for (int64_t i = 1; i < nd; i++)
    if (due_e[i] < emin) emin = due_e[i];
  m->sv0.resize(nd);
  m->si0.resize(nd);
  for (int64_t i = 0; i < nd; i++) {
    m->sv0[i] = (uint64_t)(due_e[i] - emin);
    m->si0[i] = i;
  }
  radix_argsort(m, m->sv0, m->si0, nd);
  // validate by DIRECT ROW ACCESS first: most candidates carry their
  // metadata row from push time; a candidate whose row still holds its
  // (key, sid) is decided — fire or drop — with zero hashing. Only
  // candidates whose row was freed/reused since (session fired or
  // merged) or that were pushed rowless (slow path, restore) pay the
  // probe, and those are prefetched a chunk ahead.
  constexpr int64_t CHUNK = 256;
  for (int64_t base = 0; base < nd; base += CHUNK) {
    int64_t endx = base + CHUNK < nd ? base + CHUNK : nd;
    for (int64_t x = base; x < endx; x++) {
      int32_t r = due_r[m->si0[x]];
      if (r >= 0 && r < m->capacity) {
        __builtin_prefetch(&m->row_key[r], 0, 1);
        __builtin_prefetch(&m->row_sid[r], 0, 1);
        __builtin_prefetch(&m->row_used[r], 0, 1);
      }
    }
  for (int64_t x = base; x < endx; x++) {
    int64_t i = m->si0[x];
    int64_t k = due_k[i], sid = due_s[i], e = due_e[i];
    int32_t row = due_r[i];
    if (row >= 0 && row < m->capacity && m->row_used[row] &&
        m->row_key[row] == k && m->row_sid[row] == sid) {
      // the candidate's own row is live with the same (key, sid):
      // this IS the session — validate its end in place
    } else {
      row = find_row(m, k);
      if (row < 0) {
        m->rk.push_back(k);
        m->rsid.push_back(sid);
        m->re.push_back(e);
        continue;
      }
    }
    if (m->row_sid[row] == sid && m->row_end[row] == e) {
      m->pk.push_back(k);
      m->ps.push_back(m->row_start[row]);
      m->pe.push_back(e);
      m->psid.push_back(sid);
      m->pslot.push_back(m->row_dslot[row]);
      erase_row(m, row);
    }
    // else: stale candidate of a merged/extended session — dropped
  }
  }
  *out_rest = (int64_t)m->rk.size();
  return (int64_t)m->pk.size();
}

void sx_pop_fetch(void* h, int64_t* keys, int64_t* starts, int64_t* ends,
                  int64_t* sids, int32_t* slots) {
  SessionSet* m = (SessionSet*)h;
  int64_t n = (int64_t)m->pk.size();
  memcpy(keys, m->pk.data(), sizeof(int64_t) * n);
  memcpy(starts, m->ps.data(), sizeof(int64_t) * n);
  memcpy(ends, m->pe.data(), sizeof(int64_t) * n);
  memcpy(sids, m->psid.data(), sizeof(int64_t) * n);
  memcpy(slots, m->pslot.data(), sizeof(int32_t) * n);
}

void sx_pop_fetch_rest(void* h, int64_t* keys, int64_t* sids,
                       int64_t* ends) {
  SessionSet* m = (SessionSet*)h;
  int64_t n = (int64_t)m->rk.size();
  memcpy(keys, m->rk.data(), sizeof(int64_t) * n);
  memcpy(sids, m->rsid.data(), sizeof(int64_t) * n);
  memcpy(ends, m->re.data(), sizeof(int64_t) * n);
}

// ------------------------------------------------------------------------
// Stateless host-prep sweeps (no store handle): the shard-grouping and
// record-routing passes of the engines' per-batch flow, each replacing
// half a dozen numpy passes over batch-sized arrays with one C pass.
// ------------------------------------------------------------------------

namespace {

// key -> owning shard: EXACTLY flink_tpu.state.keygroups —
// fold 64->32, murmur fmix32, % max_parallelism, then the reference's
// group->subtask formula (remapped into the local key-group range when
// the engine owns a sub-range of the global group space).
inline int64_t shard_of_key(int64_t key, int64_t maxp, int64_t P,
                            int64_t kg_first, int64_t kg_last) {
  uint32_t h = (uint32_t)(uint64_t)(key ^ (key >> 32));
  h ^= h >> 16;
  h *= 0x85EBCA6Bu;
  h ^= h >> 13;
  h *= 0xC2B2AE35u;
  h ^= h >> 16;
  int64_t g = (int64_t)(h % (uint32_t)maxp);
  if (kg_first >= 0) {
    // a group outside the engine's range is a misroute: report -1
    // (callers fail loudly). An unchecked (g - kg_first) * P / span
    // would TRUNCATE toward zero where Python floors — group
    // kg_first-1 would silently land on shard 0 instead of erroring.
    if (g < kg_first || g > kg_last) return -1;
    return ((g - kg_first) * P) / (kg_last - kg_first + 1);
  }
  return (g * P) / maxp;
}

}  // namespace

// Per-session shard assignment + stable counting sort of the LIVE
// sessions (sid >= 0) by shard, gathering the resolve columns in one
// pass. out_shard is the full per-session shard column (record routing
// reads it); the *_sorted outputs are the live sessions grouped by
// shard, within-shard session order preserved. Returns the live count.
int64_t sx_shard_group(int64_t m, const int64_t* sess_key,
                       const int64_t* sess_sid, const uint8_t* fresh,
                       const int32_t* slot_hint, const int32_t* meta_row,
                       int64_t P, int64_t maxp, int64_t kg_first,
                       int64_t kg_last, int64_t* out_shard,
                       int64_t* out_counts, int64_t* out_sorted_idx,
                       int64_t* key_sorted, int64_t* sid_sorted,
                       uint8_t* fresh_sorted, int32_t* hint_sorted,
                       int32_t* row_sorted) {
  for (int64_t p = 0; p < P; p++) out_counts[p] = 0;
  for (int64_t j = 0; j < m; j++) {
    int64_t s = shard_of_key(sess_key[j], maxp, P, kg_first, kg_last);
    // a key whose group falls outside the engine's key-group range is
    // a ROUTING BUG upstream — fail loudly (the numpy path raised from
    // bincount/index), never index out_counts out of bounds
    if (s < 0 || s >= P) return -1;
    out_shard[j] = s;
    if (sess_sid[j] >= 0) out_counts[s]++;
  }
  // exclusive prefix -> write cursors
  static thread_local std::vector<int64_t> cursor;
  cursor.resize(P);
  int64_t total = 0;
  for (int64_t p = 0; p < P; p++) {
    cursor[p] = total;
    total += out_counts[p];
  }
  for (int64_t j = 0; j < m; j++) {
    if (sess_sid[j] < 0) continue;
    int64_t pos = cursor[out_shard[j]]++;
    out_sorted_idx[pos] = j;
    key_sorted[pos] = sess_key[j];
    sid_sorted[pos] = sess_sid[j];
    fresh_sorted[pos] = fresh[j];
    hint_sorted[pos] = slot_hint[j];
    row_sorted[pos] = meta_row[j];
  }
  return total;
}

// Per-shard record counts in one pass (the batch-split working-set
// bound pays this EVERY batch): returns the max count over shards.
int64_t sx_rec_shard_max(int64_t n, const int64_t* keys, int64_t P,
                         int64_t maxp, int64_t kg_first, int64_t kg_last) {
  static thread_local std::vector<int64_t> counts;
  counts.resize(P);
  std::fill(counts.begin(), counts.end(), 0);
  for (int64_t i = 0; i < n; i++) {
    int64_t s = shard_of_key(keys[i], maxp, P, kg_first, kg_last);
    if (s < 0 || s >= P) return -1;  // misrouted key: fail loudly
    counts[s]++;
  }
  int64_t mx = 0;
  for (int64_t p = 0; p < P; p++)
    if (counts[p] > mx) mx = counts[p];
  return mx;
}

// Record routing: each record takes its session's slot and shard —
// rec[i] = per_session[rec_sess[i]], one sequential pass — with the
// resolved slots arriving as (sorted_idx, slot_sorted) pairs from the
// per-shard resolve.
void sx_route(int64_t n, int64_t m, const int32_t* rec_sess,
              int64_t n_live, const int64_t* sorted_idx,
              const int32_t* slot_sorted, const int64_t* sess_shard,
              int32_t* out_rec_slots, int64_t* out_rec_shards) {
  static thread_local std::vector<int32_t> slot_of;
  slot_of.resize(m);
  std::fill(slot_of.begin(), slot_of.end(), 0);
  for (int64_t i = 0; i < n_live; i++)
    slot_of[sorted_idx[i]] = slot_sorted[i];
  for (int64_t i = 0; i < n; i++) {
    int64_t j = rec_sess[i];
    out_rec_slots[i] = slot_of[j];
    out_rec_shards[i] = sess_shard[j];
  }
}

}  // extern "C"
