// Batch (key, namespace) -> slot hash index for the TPU slot-table state
// backend. This is the native half of the keyed-state hot path: the role the
// reference delegates to RocksDB/ForSt via JNI (batch point lookups backing
// StateExecutor.executeBatchRequests) is played here by an open-addressing
// table that maps 128-bit (key_id, namespace) pairs to dense device slot ids
// in one C call per micro-batch. No LSM is needed — persistence comes from
// logical snapshots of the slot arrays (see flink_tpu/state/slot_table.py).
//
// Design: linear-probing buckets sized 2x slot capacity (load <= 0.5),
// slot-id free list, slot 0 reserved as the identity slot, growth by
// doubling with full rebuild (bounded amortized cost, mirrors the device
// array growth in Python).
//
// Exposed as a plain C ABI for ctypes; all batch arguments are raw pointers
// into NumPy buffers.

#include <algorithm>
#include <cstdint>
#include <cstdlib>
#include <cstring>

namespace {

// Scratch of the batch sweep (sm_resolve_grouped below), kept with the map
// and reused from batch to batch: a 131k-record batch would otherwise
// fault in a megabyte of fresh pages per call.
struct SweepScratch {
  int64_t rec_cap;   // records sinv holds
  int32_t* sinv;     // [rec_cap] record -> distinct namespace (first-seen)
  int64_t uniq_cap;  // distinct namespaces the arrays below hold
  int64_t* val;      // [uniq_cap] the namespace
  int64_t* count;    // [uniq_cap] records under it
  int64_t* cursor;   // [uniq_cap] where its next new slot goes
  int32_t* order;    // [uniq_cap] first-seen indexes by ascending namespace
  int64_t tab_size;  // power of two, > 2 * distinct held
  int32_t* tab;      // namespace -> first-seen index, -1 empty
};

struct SlotMap {
  int64_t capacity;      // slot array capacity (includes reserved slot 0)
  int64_t max_capacity;  // growth bound
  int64_t used;          // live entries
  int64_t bucket_count;  // power of two, >= 2*capacity
  int32_t* buckets;      // slot id, -1 empty (deletion is backward-shift,
                         // so no tombstones ever exist)
  int64_t* slot_key;     // [capacity]
  int64_t* slot_ns;      // [capacity]
  uint8_t* slot_used;    // [capacity]
  int32_t* free_stack;   // [capacity]
  int64_t free_top;      // stack size
  SweepScratch sweep;    // zeroed by sm_create's calloc, grown on demand
};

inline uint64_t mix_hash(uint64_t k, uint64_t n) {
  uint64_t x = k ^ (n * 0x9E3779B97F4A7C15ull);
  x ^= x >> 30;
  x *= 0xBF58476D1CE4E5B9ull;
  x ^= x >> 27;
  x *= 0x94D049BB133111EBull;
  x ^= x >> 31;
  return x;
}

void build_buckets(SlotMap* m) {
  int64_t want = m->capacity * 2;
  int64_t bc = 64;
  while (bc < want) bc <<= 1;
  m->bucket_count = bc;
  free(m->buckets);
  m->buckets = (int32_t*)malloc(sizeof(int32_t) * bc);
  for (int64_t i = 0; i < bc; i++) m->buckets[i] = -1;
  uint64_t mask = (uint64_t)bc - 1;
  for (int64_t s = 1; s < m->capacity; s++) {
    if (!m->slot_used[s]) continue;
    uint64_t h = mix_hash((uint64_t)m->slot_key[s], (uint64_t)m->slot_ns[s]);
    uint64_t i = h & mask;
    while (m->buckets[i] >= 0) i = (i + 1) & mask;
    m->buckets[i] = (int32_t)s;
  }
}

// returns 0 on success, -1 if at max capacity
int grow(SlotMap* m) {
  if (m->capacity >= m->max_capacity) return -1;
  int64_t old_cap = m->capacity;
  int64_t new_cap = old_cap * 2;
  if (new_cap > m->max_capacity) new_cap = m->max_capacity;
  m->slot_key = (int64_t*)realloc(m->slot_key, sizeof(int64_t) * new_cap);
  m->slot_ns = (int64_t*)realloc(m->slot_ns, sizeof(int64_t) * new_cap);
  m->slot_used = (uint8_t*)realloc(m->slot_used, sizeof(uint8_t) * new_cap);
  m->free_stack = (int32_t*)realloc(m->free_stack, sizeof(int32_t) * new_cap);
  memset(m->slot_used + old_cap, 0, (size_t)(new_cap - old_cap));
  for (int64_t s = new_cap - 1; s >= old_cap; s--)
    m->free_stack[m->free_top++] = (int32_t)s;
  m->capacity = new_cap;
  build_buckets(m);
  return 0;
}

// The probe of one (key, ns) pair from its hash: the pair's slot, a free
// one taken where the pair is new (*is_new), the table grown where none is
// free (*grows counts them). -1: full at max_capacity.
inline int32_t probe_or_insert(SlotMap* m, int64_t k, int64_t ns,
                               uint64_t hash, int32_t* grows, bool* is_new) {
  uint64_t mask = (uint64_t)m->bucket_count - 1;
  uint64_t i = hash & mask;
  for (;;) {
    int32_t b = m->buckets[i];
    if (b == -1) {
      if (m->free_top == 0) {
        if (grow(m) != 0) return -1;
        ++*grows;
        // re-probe against rebuilt buckets
        mask = (uint64_t)m->bucket_count - 1;
        i = hash & mask;
        continue;
      }
      int32_t slot = m->free_stack[--m->free_top];
      m->buckets[i] = slot;
      m->slot_key[slot] = k;
      m->slot_ns[slot] = ns;
      m->slot_used[slot] = 1;
      m->used++;
      *is_new = true;
      return slot;
    }
    if (m->slot_key[b] == k && m->slot_ns[b] == ns) {
      *is_new = false;
      return b;
    }
    i = (i + 1) & mask;
  }
}

// The fire path's carried slot matrix (see sm_carry_advance below).
struct SliceCarry {
  int64_t k;             // columns: slices per window
  int64_t rows;          // live rows, dense in [0, rows)
  int64_t row_cap;       // rows allocated
  int64_t* keys;         // [row_cap] the row's key
  int32_t* mat;          // [row_cap * k] slot per (row, slice), 0 = absent
  int64_t bucket_count;  // power of two, >= 2 * row_cap
  int32_t* buckets;      // key -> row, -1 empty (backward-shift deletion)
  int32_t* emptied;      // [row_cap] scratch: rows an advance left empty
};

void carry_grow(SliceCarry* c) {
  int64_t cap = c->row_cap ? c->row_cap * 2 : 1024;
  c->keys = (int64_t*)realloc(c->keys, sizeof(int64_t) * cap);
  c->mat = (int32_t*)realloc(c->mat, sizeof(int32_t) * cap * c->k);
  c->emptied = (int32_t*)realloc(c->emptied, sizeof(int32_t) * cap);
  c->row_cap = cap;
  int64_t bc = c->bucket_count;
  while (bc < cap * 2) bc <<= 1;
  if (bc == c->bucket_count) return;
  c->bucket_count = bc;
  free(c->buckets);
  c->buckets = (int32_t*)malloc(sizeof(int32_t) * bc);
  memset(c->buckets, 0xff, sizeof(int32_t) * bc);
  uint64_t mask = (uint64_t)bc - 1;
  for (int64_t r = 0; r < c->rows; r++) {
    uint64_t b = mix_hash((uint64_t)c->keys[r], 0) & mask;
    while (c->buckets[b] >= 0) b = (b + 1) & mask;
    c->buckets[b] = (int32_t)r;
  }
}

inline uint64_t carry_bucket_of(const SliceCarry* c, int64_t key) {
  uint64_t mask = (uint64_t)c->bucket_count - 1;
  uint64_t b = mix_hash((uint64_t)key, 0) & mask;
  while (c->keys[c->buckets[b]] != key) b = (b + 1) & mask;
  return b;
}

// Drop row r (its last cell left): its key leaves the table, and the last
// row takes its place so the rows stay dense.
void carry_remove_row(SliceCarry* c, int64_t r) {
  uint64_t mask = (uint64_t)c->bucket_count - 1;
  uint64_t hole = carry_bucket_of(c, c->keys[r]);
  for (uint64_t j = (hole + 1) & mask; c->buckets[j] != -1;
       j = (j + 1) & mask) {
    uint64_t home = mix_hash((uint64_t)c->keys[c->buckets[j]], 0) & mask;
    if (((j - home) & mask) >= ((j - hole) & mask)) {
      c->buckets[hole] = c->buckets[j];
      hole = j;
    }
  }
  c->buckets[hole] = -1;
  int64_t last = --c->rows;
  if (r == last) return;
  c->buckets[carry_bucket_of(c, c->keys[last])] = (int32_t)r;
  c->keys[r] = c->keys[last];
  memcpy(c->mat + r * c->k, c->mat + last * c->k, sizeof(int32_t) * c->k);
}

inline uint64_t sweep_hash(int64_t v) {
  return (uint64_t)v * 0x9E3779B97F4A7C15ull >> 20;
}

// First-seen index of namespace v among the *k held so far, entering it
// where it is new; -1 where that would pass max_uniq.
inline int32_t sweep_index_of(SweepScratch* w, int64_t v, int64_t* k,
                              int64_t max_uniq) {
  uint64_t mask = (uint64_t)w->tab_size - 1;
  uint64_t b = sweep_hash(v) & mask;
  for (int32_t i; (i = w->tab[b]) >= 0; b = (b + 1) & mask)
    if (w->val[i] == v) return i;
  if (*k >= max_uniq) return -1;
  if (*k == w->uniq_cap) {
    int64_t cap = w->uniq_cap ? w->uniq_cap * 2 : 64;
    w->val = (int64_t*)realloc(w->val, sizeof(int64_t) * cap);
    w->count = (int64_t*)realloc(w->count, sizeof(int64_t) * cap);
    w->cursor = (int64_t*)realloc(w->cursor, sizeof(int64_t) * cap);
    w->order = (int32_t*)realloc(w->order, sizeof(int32_t) * cap);
    w->uniq_cap = cap;
  }
  int32_t i = (int32_t)(*k)++;
  w->val[i] = v;
  w->count[i] = 0;
  if (*k * 2 < w->tab_size) {
    w->tab[b] = i;
    return i;
  }
  // keep the table under half full: double it and enter everything again
  w->tab_size *= 2;
  w->tab = (int32_t*)realloc(w->tab, sizeof(int32_t) * w->tab_size);
  memset(w->tab, 0xff, sizeof(int32_t) * w->tab_size);
  mask = (uint64_t)w->tab_size - 1;
  for (int32_t j = 0; j <= i; j++) {
    b = sweep_hash(w->val[j]) & mask;
    while (w->tab[b] >= 0) b = (b + 1) & mask;
    w->tab[b] = j;
  }
  return i;
}

}  // namespace

extern "C" {

void* sm_create(int64_t initial_capacity, int64_t max_capacity) {
  if (initial_capacity < 1024) initial_capacity = 1024;
  if (max_capacity < initial_capacity) max_capacity = initial_capacity;
  SlotMap* m = (SlotMap*)calloc(1, sizeof(SlotMap));
  m->capacity = initial_capacity;
  m->max_capacity = max_capacity;
  m->slot_key = (int64_t*)calloc(initial_capacity, sizeof(int64_t));
  m->slot_ns = (int64_t*)calloc(initial_capacity, sizeof(int64_t));
  m->slot_used = (uint8_t*)calloc(initial_capacity, 1);
  m->free_stack = (int32_t*)malloc(sizeof(int32_t) * initial_capacity);
  m->free_top = 0;
  for (int64_t s = initial_capacity - 1; s >= 1; s--)
    m->free_stack[m->free_top++] = (int32_t)s;
  m->buckets = nullptr;
  build_buckets(m);
  return m;
}

void sm_destroy(void* h) {
  SlotMap* m = (SlotMap*)h;
  free(m->buckets);
  free(m->slot_key);
  free(m->slot_ns);
  free(m->slot_used);
  free(m->free_stack);
  free(m->sweep.sinv);
  free(m->sweep.val);
  free(m->sweep.count);
  free(m->sweep.cursor);
  free(m->sweep.order);
  free(m->sweep.tab);
  free(m);
}

int64_t sm_capacity(void* h) { return ((SlotMap*)h)->capacity; }
int64_t sm_used(void* h) { return ((SlotMap*)h)->used; }
const int64_t* sm_slot_keys(void* h) { return ((SlotMap*)h)->slot_key; }
const int64_t* sm_slot_namespaces(void* h) { return ((SlotMap*)h)->slot_ns; }
const uint8_t* sm_slot_used(void* h) { return ((SlotMap*)h)->slot_used; }

// Batch lookup-or-insert. Duplicates within the batch are fine (first
// occurrence inserts, later ones find). out_is_new[i]=1 iff record i
// performed the insert. Returns:
//   >=0 : number of grows that occurred (caller must re-wrap slot arrays)
//   -1  : table full at max_capacity
int32_t sm_lookup_or_insert(void* h, int64_t n, const int64_t* keys,
                            const int64_t* nss, int32_t* out_slots,
                            uint8_t* out_is_new) {
  SlotMap* m = (SlotMap*)h;
  int32_t grows = 0;
  // Chunked software prefetch: the table spans far more than L2, so the
  // bucket probe and the slot_key/slot_ns verify are each a likely cache
  // miss. Hash a chunk up front, prefetch every home bucket line, then
  // peek the (now warm) buckets to prefetch the slot rows. Inserts during
  // processing only make earlier hints stale — hints are never required
  // for correctness.
  constexpr int64_t CHUNK = 256;
  uint64_t hashes[CHUNK];
  for (int64_t base = 0; base < n; base += CHUNK) {
    int64_t end = base + CHUNK < n ? base + CHUNK : n;
    uint64_t pmask = (uint64_t)m->bucket_count - 1;
    for (int64_t r = base; r < end; r++) {
      uint64_t hh = mix_hash((uint64_t)keys[r], (uint64_t)nss[r]);
      hashes[r - base] = hh;
      __builtin_prefetch(&m->buckets[hh & pmask], 0, 1);
    }
    for (int64_t r = base; r < end; r++) {
      int32_t b = m->buckets[hashes[r - base] & pmask];
      if (b >= 0) {
        __builtin_prefetch(&m->slot_key[b], 0, 1);
        __builtin_prefetch(&m->slot_ns[b], 0, 1);
      }
    }
    for (int64_t r = base; r < end; r++) {
      bool is_new;
      int32_t slot = probe_or_insert(m, keys[r], nss[r], hashes[r - base],
                                     &grows, &is_new);
      if (slot < 0) return -1;
      out_slots[r] = slot;
      if (out_is_new) out_is_new[r] = is_new;
    }
  }
  return grows;
}

// One sweep that resolves a whole batch: (key, namespace) -> slot for
// every record, and the slots newly given out grouped by namespace — what
// the registry (flink_tpu/state/slot_table.py) appends, with no is_new
// mask, sort or split behind the call.
//
// ``vals`` are timestamps where width > 0: a record's namespace is then
// the end of its slice, ts - floormod(ts - offset, width) + width (the
// assigner's rule; an in-order run stays in the slice of the record before
// it, so a compare stands in for the division). Where width == 0 they are
// the namespaces themselves.
//
// Pass A reads ``vals`` alone and changes nothing: each record's distinct
// namespace (first-seen index, kept in scratch) and the records under
// each. More than max_uniq distinct namespaces, or (width > 0) a slice end
// below live_from — a late record, which the caller's own path drops and
// counts — returns -2 with the table untouched. The counts give each
// namespace its place in out_new, ascending by namespace, before the first
// insert. Pass B is sm_lookup_or_insert's probe (same hash, prefetch,
// growth), a new slot going to its namespace's cursor: new slots come out
// grouped, in record order within a group.
//
// out_groups is [3, max_uniq] int64: the distinct namespaces ascending,
// the records under each, the new slots of each. Namespace j's new slots
// start at out_new[records of the namespaces before it]. *out_k = distinct
// namespaces. Returns grows (>= 0) or -1 (table full at max_capacity;
// out_groups and out_new then hold what was inserted before it, so the
// caller's registry can stay level with the table).
int32_t sm_resolve_grouped(void* h, int64_t n, const int64_t* keys,
                           const int64_t* vals, int64_t offset,
                           int64_t width, int64_t live_from,
                           int64_t max_uniq, int32_t* out_slots,
                           int32_t* out_new, int64_t* out_groups,
                           int64_t* out_k) {
  SlotMap* m = (SlotMap*)h;
  SweepScratch* w = &m->sweep;
  if (n > w->rec_cap) {
    free(w->sinv);
    w->sinv = (int32_t*)malloc(sizeof(int32_t) * n);
    w->rec_cap = n;
  }
  if (!w->tab) {
    w->tab_size = 64;
    w->tab = (int32_t*)malloc(sizeof(int32_t) * w->tab_size);
  }
  memset(w->tab, 0xff, sizeof(int32_t) * w->tab_size);
  int64_t k = 0;
  int32_t* sinv = w->sinv;
  // ---- pass A: distinct namespaces and their record counts
  {
    int32_t cur = -1;
    int64_t run = 0;       // records of the open run, not yet counted
    int64_t cur_val = 0;   // width > 0: the open slice's start
    for (int64_t r = 0; r < n; r++) {
      int64_t v = vals[r];
      bool same = width > 0
                      ? (uint64_t)v - (uint64_t)cur_val < (uint64_t)width
                      : v == cur_val;
      if (cur < 0 || !same) {
        if (cur >= 0) w->count[cur] += run;
        run = 0;
        int64_t ns = v;
        if (width > 0) {
          int64_t rem = (v - offset) % width;
          if (rem < 0) rem += width;
          cur_val = v - rem;
          ns = cur_val + width;
          if (ns < live_from) return -2;
        } else {
          cur_val = v;
        }
        cur = sweep_index_of(w, ns, &k, max_uniq);
        if (cur < 0) return -2;
      }
      sinv[r] = cur;
      run++;
    }
    if (cur >= 0) w->count[cur] += run;
  }
  for (int64_t j = 0; j < k; j++) w->order[j] = (int32_t)j;
  std::sort(w->order, w->order + k,
            [w](int32_t a, int32_t b) { return w->val[a] < w->val[b]; });
  int64_t* g_val = out_groups;
  int64_t* g_records = out_groups + max_uniq;
  int64_t* g_new = out_groups + 2 * max_uniq;
  int64_t pos = 0;
  for (int64_t j = 0; j < k; j++) {
    int32_t u = w->order[j];
    g_val[j] = w->val[u];
    g_records[j] = w->count[u];
    w->cursor[u] = pos;
    pos += w->count[u];
  }
  *out_k = k;
  // ---- pass B: the probe (sm_lookup_or_insert's, see there)
  int32_t grows = 0;
  bool full = false;
  constexpr int64_t CHUNK = 256;
  uint64_t hashes[CHUNK];
  for (int64_t base = 0; base < n && !full; base += CHUNK) {
    int64_t end = base + CHUNK < n ? base + CHUNK : n;
    uint64_t pmask = (uint64_t)m->bucket_count - 1;
    for (int64_t r = base; r < end; r++) {
      uint64_t hh = mix_hash((uint64_t)keys[r], (uint64_t)w->val[sinv[r]]);
      hashes[r - base] = hh;
      __builtin_prefetch(&m->buckets[hh & pmask], 0, 1);
    }
    for (int64_t r = base; r < end; r++) {
      int32_t b = m->buckets[hashes[r - base] & pmask];
      if (b >= 0) {
        __builtin_prefetch(&m->slot_key[b], 0, 1);
        __builtin_prefetch(&m->slot_ns[b], 0, 1);
      }
    }
    for (int64_t r = base; r < end; r++) {
      int32_t u = sinv[r];
      bool is_new;
      int32_t slot = probe_or_insert(m, keys[r], w->val[u], hashes[r - base],
                                     &grows, &is_new);
      if (slot < 0) {
        full = true;
        break;
      }
      out_slots[r] = slot;
      if (is_new) out_new[w->cursor[u]++] = slot;
    }
  }
  pos = 0;
  for (int64_t j = 0; j < k; j++) {
    int32_t u = w->order[j];
    g_new[j] = w->cursor[u] - pos;
    pos += w->count[u];
  }
  return full ? -1 : grows;
}

// Read-only batch probe: out_slots[i] = slot id, or -1 if the pair is not
// present. Never inserts — this is the queryable-state point-lookup path
// (the role of the reference's QueryableStateClient -> KvStateServer
// lookups against the live backend).
void sm_lookup(void* h, int64_t n, const int64_t* keys, const int64_t* nss,
               int32_t* out_slots) {
  SlotMap* m = (SlotMap*)h;
  uint64_t mask = (uint64_t)m->bucket_count - 1;
  constexpr int64_t CHUNK = 256;
  uint64_t hashes[CHUNK];
  for (int64_t base = 0; base < n; base += CHUNK) {
    int64_t end = base + CHUNK < n ? base + CHUNK : n;
    for (int64_t r = base; r < end; r++) {
      uint64_t hh = mix_hash((uint64_t)keys[r], (uint64_t)nss[r]);
      hashes[r - base] = hh;
      __builtin_prefetch(&m->buckets[hh & mask], 0, 1);
    }
    for (int64_t r = base; r < end; r++) {
      int32_t b = m->buckets[hashes[r - base] & mask];
      if (b >= 0) {
        __builtin_prefetch(&m->slot_key[b], 0, 1);
        __builtin_prefetch(&m->slot_ns[b], 0, 1);
      }
    }
    for (int64_t r = base; r < end; r++) {
      int64_t k = keys[r], ns = nss[r];
      uint64_t i = hashes[r - base] & mask;
      out_slots[r] = -1;
      for (;;) {
        int32_t b = m->buckets[i];
        if (b == -1) break;
        if (m->slot_key[b] == k && m->slot_ns[b] == ns) {
          out_slots[r] = b;
          break;
        }
        i = (i + 1) & mask;
      }
    }
  }
}

// Verify folded slot hints against the table's own metadata: out[i] is
// hints[i] iff the table currently maps (keys[i], nss[i]) at exactly
// that slot, else -1 (caller falls back to the hash probe there). A
// passing verification can never name a wrong row — this IS the
// table's content. One direct-indexed pass; no hashing.
void sm_verify(void* h, int64_t n, const int64_t* keys, const int64_t* nss,
               const int32_t* hints, int32_t* out_slots) {
  SlotMap* m = (SlotMap*)h;
  constexpr int64_t CHUNK = 256;
  for (int64_t base = 0; base < n; base += CHUNK) {
    int64_t end = base + CHUNK < n ? base + CHUNK : n;
    for (int64_t r = base; r < end; r++) {
      int32_t s = hints[r];
      if (s >= 0 && s < m->capacity) {
        __builtin_prefetch(&m->slot_used[s], 0, 1);
        __builtin_prefetch(&m->slot_key[s], 0, 1);
        __builtin_prefetch(&m->slot_ns[s], 0, 1);
      }
    }
    for (int64_t r = base; r < end; r++) {
      int32_t s = hints[r];
      out_slots[r] = (s >= 0 && s < m->capacity && m->slot_used[s] &&
                      m->slot_key[s] == keys[r] && m->slot_ns[s] == nss[r])
                         ? s
                         : -1;
    }
  }
}

// Erase pairs; writes freed slot ids to out_slots (only for pairs that were
// present). Returns the number actually erased. Deletion is backward-shift
// (Knuth 6.4 algorithm R): no tombstones, so probe chains stay short under
// the insert/erase churn of session windows and slice expiry.
int64_t sm_erase(void* h, int64_t n, const int64_t* keys, const int64_t* nss,
                 int32_t* out_slots) {
  SlotMap* m = (SlotMap*)h;
  int64_t erased = 0;
  uint64_t mask = (uint64_t)m->bucket_count - 1;
  constexpr int64_t CHUNK = 256;
  uint64_t hashes[CHUNK];
  for (int64_t base = 0; base < n; base += CHUNK) {
    int64_t end = base + CHUNK < n ? base + CHUNK : n;
    // chunked prefetch (same discipline as the probe paths): session
    // fires erase tens of thousands of scattered pairs per watermark,
    // each probe a likely miss. Erases inside the chunk only stale the
    // hints — correctness never depends on them.
    for (int64_t r = base; r < end; r++) {
      uint64_t hh = mix_hash((uint64_t)keys[r], (uint64_t)nss[r]);
      hashes[r - base] = hh;
      __builtin_prefetch(&m->buckets[hh & mask], 0, 1);
    }
    for (int64_t r = base; r < end; r++) {
      int32_t b = m->buckets[hashes[r - base] & mask];
      if (b >= 0) {
        __builtin_prefetch(&m->slot_key[b], 0, 1);
        __builtin_prefetch(&m->slot_ns[b], 0, 1);
      }
    }
  for (int64_t r = base; r < end; r++) {
    int64_t k = keys[r], ns = nss[r];
    uint64_t i = hashes[r - base] & mask;
    for (;;) {
      int32_t b = m->buckets[i];
      if (b == -1) break;  // not present
      if (m->slot_key[b] == k && m->slot_ns[b] == ns) {
        m->slot_used[b] = 0;
        m->free_stack[m->free_top++] = b;
        m->used--;
        out_slots[erased++] = b;
        // backward-shift: compact the probe chain following i
        uint64_t hole = i;
        uint64_t j = (i + 1) & mask;
        while (m->buckets[j] != -1) {
          int32_t c = m->buckets[j];
          uint64_t home =
              mix_hash((uint64_t)m->slot_key[c], (uint64_t)m->slot_ns[c]) &
              mask;
          // move c into the hole if its home position does not lie
          // (cyclically) strictly after the hole
          uint64_t dist_home = (j - home) & mask;
          uint64_t dist_hole = (j - hole) & mask;
          if (dist_home >= dist_hole) {
            m->buckets[hole] = c;
            hole = j;
          }
          j = (j + 1) & mask;
        }
        m->buckets[hole] = -1;
        break;
      }
      i = (i + 1) & mask;
    }
  }
  }
  return erased;
}

// Fused pane-table ingest, pass A — ONE sweep over the micro-batch doing
// what previously took five numpy passes plus a separate native probe:
//   - slice end per record from its timestamp (aligned windows, floor-mod
//     so pre-epoch timestamps match numpy's np.remainder semantics):
//       se = ts - floormod(ts - offset, width) + width
//   - key -> dense column via the same probe as sm_lookup_or_insert
//     (namespace fixed at 0: a pane-table column is keyed by key only)
//   - distinct slice ends tracked first-seen through a small open hash
// Outputs: out_cols[n] (i32 column ids), out_is_new[n], out_sinv[n]
// (i32 index into out_uniq), out_uniq[maxu] (i64 distinct slice ends,
// first-seen order), *out_k (distinct count), *out_max_col.
// Returns grows (>=0), -1 table full, -2 more than maxu distinct slice
// ends (caller falls back to the unfused path).
int32_t sm_pane_ingest(void* h, int64_t n, const int64_t* keys,
                       const int64_t* ts, int64_t offset, int64_t width,
                       int64_t maxu, int32_t* out_cols, uint8_t* out_is_new,
                       int32_t* out_sinv, int64_t* out_uniq, int64_t* out_k,
                       int64_t* out_max_col) {
  SlotMap* m = (SlotMap*)h;
  int32_t grows = 0;
  // distinct-slice-end scratch hash (tiny: slices per batch is a handful)
  uint64_t nb = 64;
  while (nb < (uint64_t)maxu * 2) nb <<= 1;
  int64_t* se_key = (int64_t*)malloc(sizeof(int64_t) * nb);
  int32_t* se_idx = (int32_t*)malloc(sizeof(int32_t) * nb);
  memset(se_idx, 0xff, sizeof(int32_t) * nb);
  int64_t k_count = 0;
  int64_t max_col = 0;
  constexpr int64_t CHUNK = 256;
  uint64_t hashes[CHUNK];
  for (int64_t base = 0; base < n; base += CHUNK) {
    int64_t end = base + CHUNK < n ? base + CHUNK : n;
    uint64_t pmask = (uint64_t)m->bucket_count - 1;
    for (int64_t r = base; r < end; r++) {
      uint64_t hh = mix_hash((uint64_t)keys[r], 0);
      hashes[r - base] = hh;
      __builtin_prefetch(&m->buckets[hh & pmask], 0, 1);
    }
    for (int64_t r = base; r < end; r++) {
      int32_t b = m->buckets[hashes[r - base] & pmask];
      if (b >= 0) __builtin_prefetch(&m->slot_key[b], 0, 1);
    }
    for (int64_t r = base; r < end; r++) {
      // slice end (floor-mod)
      int64_t x = ts[r] - offset;
      int64_t rem = x % width;
      if (rem < 0) rem += width;
      int64_t se = ts[r] - rem + width;
      uint64_t sb = mix_hash((uint64_t)se, 0) & (nb - 1);
      for (;;) {
        if (se_idx[sb] < 0) {
          if (k_count >= maxu) {
            free(se_key);
            free(se_idx);
            return -2;
          }
          se_key[sb] = se;
          se_idx[sb] = (int32_t)k_count;
          out_uniq[k_count++] = se;
          break;
        }
        if (se_key[sb] == se) break;
        sb = (sb + 1) & (nb - 1);
      }
      out_sinv[r] = se_idx[sb];
      // key -> column (lookup-or-insert, ns = 0)
      bool is_new;
      int32_t col = probe_or_insert(m, keys[r], 0, hashes[r - base], &grows,
                                    &is_new);
      if (col < 0) {
        free(se_key);
        free(se_idx);
        return -1;
      }
      out_cols[r] = col;
      out_is_new[r] = is_new;
      if (col > max_col) max_col = col;
    }
  }
  free(se_key);
  free(se_idx);
  *out_k = k_count;
  *out_max_col = max_col;
  return grows;
}

// Fused pane-table ingest, pass B: the flat i32 scatter index from the
// pass-A columns + the ring rows Python allocated for the distinct slice
// ends (row allocation may grow device arrays, so it stays in Python).
void sm_flat_fuse(int64_t n, const int32_t* cols, const int32_t* sinv,
                  const int64_t* rowmap, int64_t capacity,
                  int32_t* out_flat) {
  for (int64_t i = 0; i < n; i++) {
    out_flat[i] = (int32_t)(rowmap[sinv[i]] * capacity + (int64_t)cols[i]);
  }
}

// ---- the fire path's carried slot matrix -------------------------------
//
// A window's (keys, [rows, k] slot matrix) kept from one fire to the next
// (flink_tpu/state/slot_table.py, _NamespaceRegistry.slice_matrix). A
// sliding window shares k-1 of its k slices with the window before it, so
// an advance drops the columns that left, probes only the cells that
// entered into a key -> row table that persists between calls, and sweeps
// out the rows whose last cell left. The same call from an empty carry is
// the from-nothing rebuild. Rows stay dense (an emptied row is overwritten
// by the last one), so row order is arbitrary; columns are the caller's
// slice order.

void* sm_carry_create() {
  SliceCarry* c = (SliceCarry*)calloc(1, sizeof(SliceCarry));
  c->bucket_count = 64;
  c->buckets = (int32_t*)malloc(sizeof(int32_t) * c->bucket_count);
  memset(c->buckets, 0xff, sizeof(int32_t) * c->bucket_count);
  return c;
}

void sm_carry_destroy(void* h) {
  SliceCarry* c = (SliceCarry*)h;
  free(c->keys);
  free(c->mat);
  free(c->buckets);
  free(c->emptied);
  free(c);
}

// One fire's advance. ``shift`` columns leave on the left (shift >= k, or
// another k than the carry holds: everything leaves — a rebuild). The
// cells that entered come as ``n_seg`` runs of ``slots``: run s holds
// seg_len[s] slots of column seg_col[s]; a cell's key is
// slot_key[slot]. Writes the advanced (keys, matrix) to out_keys /
// out_mat, which the caller sized for the carry's rows + the cells
// given, and returns the rows written. The out arrays are the caller's:
// the carry never touches them again.
int64_t sm_carry_advance(void* h, int64_t k, int64_t shift, int64_t n_seg,
                         const int32_t* seg_col, const int64_t* seg_len,
                         const int32_t* slots, const int64_t* slot_key,
                         int64_t* out_keys, int32_t* out_mat) {
  SliceCarry* c = (SliceCarry*)h;
  int64_t n_emptied = 0;
  if (k != c->k || shift >= k) {
    if (k != c->k) {
      if (c->row_cap && k)
        c->mat = (int32_t*)realloc(c->mat, sizeof(int32_t) * c->row_cap * k);
      c->k = k;
    }
    c->rows = 0;
    memset(c->buckets, 0xff, sizeof(int32_t) * c->bucket_count);
  } else if (shift > 0) {
    // every row moves left at once (rows are contiguous: one memmove of
    // the flat matrix), which leaves the next row's first cells in each
    // row's tail: clear those, and note the rows left empty. Most of
    // their keys come back with the slice that enters, so they are
    // judged after it
    if (c->rows)
      memmove(c->mat, c->mat + shift,
              sizeof(int32_t) * (c->rows * k - shift));
    for (int64_t r = c->rows - 1; r >= 0; r--) {
      int32_t* row = c->mat + r * k;
      int32_t live = 0;
      for (int64_t j = 0; j < k - shift; j++) live |= row[j];
      for (int64_t j = k - shift; j < k; j++) row[j] = 0;
      if (!live) c->emptied[n_emptied++] = (int32_t)r;
    }
  }
  constexpr int64_t CHUNK = 256;
  int64_t ck[CHUNK];
  uint64_t hashes[CHUNK];
  const int32_t* seg_slots = slots;
  for (int64_t s = 0; s < n_seg; seg_slots += seg_len[s], s++) {
    int64_t col = seg_col[s], n = seg_len[s];
    for (int64_t base = 0; base < n; base += CHUNK) {
      int64_t end = base + CHUNK < n ? base + CHUNK : n;
      // same prefetch discipline as the probe paths: slot_key is far
      // larger than the caches, and every gather a likely miss
      for (int64_t i = base; i < end; i++)
        __builtin_prefetch(&slot_key[seg_slots[i]], 0, 1);
      for (int64_t i = base; i < end; i++) {
        ck[i - base] = slot_key[seg_slots[i]];
        hashes[i - base] = mix_hash((uint64_t)ck[i - base], 0);
        __builtin_prefetch(
            &c->buckets[hashes[i - base] & ((uint64_t)c->bucket_count - 1)],
            0, 1);
      }
      for (int64_t i = base; i < end; i++) {
        int32_t r =
            c->buckets[hashes[i - base] & ((uint64_t)c->bucket_count - 1)];
        if (r >= 0) {
          __builtin_prefetch(&c->keys[r], 0, 1);
          __builtin_prefetch(&c->mat[(int64_t)r * k + col], 1, 1);
        }
      }
      for (int64_t i = base; i < end; i++) {
        int64_t key = ck[i - base];
        uint64_t mask = (uint64_t)c->bucket_count - 1;
        uint64_t b = hashes[i - base] & mask;
        int32_t r;
        for (;;) {
          r = c->buckets[b];
          if (r < 0) {
            if (c->rows == c->row_cap) {
              carry_grow(c);
              mask = (uint64_t)c->bucket_count - 1;
              b = hashes[i - base] & mask;
              continue;
            }
            r = (int32_t)c->rows++;
            c->buckets[b] = r;
            c->keys[r] = key;
            memset(c->mat + (int64_t)r * k, 0, sizeof(int32_t) * k);
            break;
          }
          if (c->keys[r] == key) break;
          b = (b + 1) & mask;
        }
        c->mat[(int64_t)r * k + col] = seg_slots[i];
      }
    }
  }
  // rows still empty go, highest first: whatever lies above the one
  // being judged is live, so the last row may fill its place
  for (int64_t i = 0; i < n_emptied; i++) {
    const int32_t* row = c->mat + (int64_t)c->emptied[i] * k;
    int32_t live = 0;
    for (int64_t j = 0; j < k; j++) live |= row[j];
    if (!live) carry_remove_row(c, c->emptied[i]);
  }
  memcpy(out_keys, c->keys, sizeof(int64_t) * c->rows);
  memcpy(out_mat, c->mat, sizeof(int32_t) * c->rows * k);
  return c->rows;
}

}  // extern "C"
