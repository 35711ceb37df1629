// Batch (key, namespace) -> slot hash index for the TPU slot-table state
// backend. This is the native half of the keyed-state hot path: the role the
// reference delegates to RocksDB/ForSt via JNI (batch point lookups backing
// StateExecutor.executeBatchRequests) is played here by an open-addressing
// index that maps 128-bit (key_id, namespace) pairs to dense device slot ids
// in one C call per micro-batch. No LSM is needed — persistence comes from
// logical snapshots of the slot arrays (see flink_tpu/state/slot_table.py).
//
// One of everything but the probe and the erase: slot ids, the per-slot
// metadata (slot_key / slot_ns / slot_used), the free stack, slot 0 reserved
// as the identity slot, growth by doubling (mirrors the device array growth
// in Python), the batch sweep's pass over the namespaces. The pair -> slot
// map itself has two forms, chosen at sm_create by what the owner does:
//
// - partitioned (the owner frees by namespace: window slices, keyed state):
//   a directory namespace -> table and, per namespace, one open-addressing
//   table keyed by the key alone with the key stored in the bucket beside
//   the slot id, plus the namespace's keys[] / slots[] dense in insertion
//   order. A namespace's pairs live together and leave together: the retire
//   hands out slots[] and drops the table, the fire's resolve reads
//   (keys[], slots[]) from where it stopped, a probe touches one table.
// - flat (the owner frees by slot: session tables, one row per namespace,
//   millions of namespaces): one table over (key, namespace) whose buckets
//   hold slot ids and whose compares read slot_key / slot_ns, sized 2x the
//   slot capacity and rebuilt on growth.
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
  int64_t* fresh;    // [uniq_cap] pairs of it newly given a slot
  int32_t* order;    // [uniq_cap] first-seen indexes by ascending namespace
  int32_t* table;    // [uniq_cap] partitioned: its table's index
  int64_t tab_size;  // power of two, > 2 * distinct held
  int32_t* tab;      // namespace -> first-seen index, -1 empty
  // the sweep over several maps (sm_resolve_grouped_sharded), kept with
  // the first of them
  int64_t cell_cap;  // (distinct namespace, shard) cells ``cell`` holds
  int32_t* cell;     // [cell_cap] whether the cell holds a record, then
                     // its table (-1: none)
};

// ---- the partitioned form: one table per namespace

struct NsBucket {
  int64_t key;
  int32_t slot;  // 0 = empty (slot 0 is the reserved identity slot)
  int32_t pad;
};

// Memory of one table: keys[cap] and slots[cap] in one block, the buckets
// (2 * cap of them; none while cap <= TINY_CAP, a table that small is
// scanned) in another.
struct NsBlock {
  int64_t cap;
  int64_t* keys;
  int32_t* slots;
  int64_t nb;
  NsBucket* buckets;
};

struct NsTable {
  int64_t ns;
  uint64_t gen;  // order of opening: a later table of the same name differs
  int64_t n;     // pairs held: keys[0, n) / slots[0, n) in insertion order
  NsBlock mem;
  bool thinned;  // sm_erase took pairs out and has not closed the gaps yet
};

constexpr int64_t TINY_CAP = 8;   // pairs a table holds without buckets
constexpr int64_t FIRST_CAP = 4;  // pairs a new table opens for
constexpr int POOL_MAX = 4;       // dropped tables' memory kept for reuse

struct SlotMap {
  int64_t capacity;      // slot array capacity (includes reserved slot 0)
  int64_t max_capacity;  // growth bound
  int64_t used;          // live entries
  int64_t* slot_key;     // [capacity]
  int64_t* slot_ns;      // [capacity]
  uint8_t* slot_used;    // [capacity]
  int32_t* free_stack;   // [capacity]
  int64_t free_top;      // stack size
  SweepScratch sweep;    // zeroed by sm_create's calloc, grown on demand
  bool partitioned;
  // flat form
  int64_t bucket_count;  // power of two, >= 2*capacity
  int32_t* buckets;      // slot id, -1 empty (deletion is backward-shift,
                         // so no tombstones ever exist)
  // partitioned form (a flat map keeps the directory too, empty: every
  // entry that asks for a namespace's table finds none)
  NsTable* tabs;         // [tabs_cap] tables by index; live ones are in dir
  int64_t tabs_cap;
  int64_t tabs_top;      // indexes ever handed out
  int32_t* tab_free;     // [tabs_cap] indexes of closed tables
  int64_t tab_free_top;
  int64_t tabs_live;
  int32_t* dir;          // namespace -> table index, -1 empty (open hash,
                         // backward-shift deletion)
  int64_t dir_size;      // power of two, > 2 * tabs_live
  uint64_t next_gen;
  uint64_t thinned;      // times sm_erase closed gaps in some keys[]/slots[]
  NsBlock pool[POOL_MAX];
  int pool_n;
  int32_t* thin_list;    // sm_erase scratch: tables it thinned
  int64_t thin_cap;
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

// Deletion from an open-addressing table without tombstones (Knuth 6.4
// algorithm R, backward shift): the bucket at ``hole`` was vacated; every
// follower of its chain whose home does not lie (cyclically) strictly
// after the hole moves back into it. ``empty(j)`` ends the chain,
// ``home(j)`` is bucket j's entry's first choice, ``move(to, from)`` moves
// an entry. Returns the bucket left vacant, for the caller to mark empty.
template <class Empty, class Home, class Move>
inline uint64_t close_hole(uint64_t hole, uint64_t mask, Empty empty,
                           Home home, Move move) {
  for (uint64_t j = (hole + 1) & mask; !empty(j); j = (j + 1) & mask) {
    if (((j - home(j)) & mask) >= ((j - hole) & mask)) {
      move(hole, j);
      hole = j;
    }
  }
  return hole;
}

void flat_build_buckets(SlotMap* m) {
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

// Double the slot arrays. Returns 0 on success, -1 if at max capacity. The
// per-namespace tables hold slot ids and are untouched by it; the flat
// table is sized by the capacity and rebuilt.
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
  if (!m->partitioned) flat_build_buckets(m);
  return 0;
}

// A free slot for a new pair, the slot arrays grown where none is left
// (*grows counts them). -1: full at max_capacity, nothing changed.
inline int32_t take_slot(SlotMap* m, int64_t k, int64_t ns, int32_t* grows) {
  if (m->free_top == 0) {
    if (grow(m) != 0) return -1;
    ++*grows;
  }
  int32_t slot = m->free_stack[--m->free_top];
  m->slot_key[slot] = k;
  m->slot_ns[slot] = ns;
  m->slot_used[slot] = 1;
  m->used++;
  return slot;
}

inline void release_slot(SlotMap* m, int32_t slot) {
  m->slot_used[slot] = 0;
  m->free_stack[m->free_top++] = slot;
  m->used--;
}

// ---- flat form: the probe of one (key, ns) pair from its hash: the pair's
// slot, a free one taken where the pair is new (*is_new). -1: full.
inline int32_t flat_probe_or_insert(SlotMap* m, int64_t k, int64_t ns,
                                    uint64_t hash, int32_t* grows,
                                    bool* is_new) {
  uint64_t mask = (uint64_t)m->bucket_count - 1;
  uint64_t i = hash & mask;
  for (;;) {
    int32_t b = m->buckets[i];
    if (b == -1) {
      int32_t before = *grows;
      int32_t slot = take_slot(m, k, ns, grows);
      if (slot < 0) return -1;
      if (*grows != before) {
        // the buckets were rebuilt: find the pair's empty bucket again
        mask = (uint64_t)m->bucket_count - 1;
        i = hash & mask;
        while (m->buckets[i] != -1) i = (i + 1) & mask;
      }
      m->buckets[i] = slot;
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

inline int32_t flat_find(const SlotMap* m, int64_t k, int64_t ns,
                         uint64_t hash) {
  uint64_t mask = (uint64_t)m->bucket_count - 1;
  for (uint64_t i = hash & mask;; i = (i + 1) & mask) {
    int32_t b = m->buckets[i];
    if (b == -1) return -1;
    if (m->slot_key[b] == k && m->slot_ns[b] == ns) return b;
  }
}

// Erase one pair: its slot, or -1 where the table does not hold it.
inline int32_t flat_erase(SlotMap* m, int64_t k, int64_t ns, uint64_t hash) {
  uint64_t mask = (uint64_t)m->bucket_count - 1;
  uint64_t i = hash & mask;
  for (;; i = (i + 1) & mask) {
    int32_t b = m->buckets[i];
    if (b == -1) return -1;
    if (m->slot_key[b] == k && m->slot_ns[b] == ns) break;
  }
  int32_t slot = m->buckets[i];
  int32_t* bk = m->buckets;
  m->buckets[close_hole(
      i, mask, [bk](uint64_t j) { return bk[j] == -1; },
      [m, bk, mask](uint64_t j) {
        return mix_hash((uint64_t)m->slot_key[bk[j]],
                        (uint64_t)m->slot_ns[bk[j]]) & mask;
      },
      [bk](uint64_t to, uint64_t from) { bk[to] = bk[from]; })] = -1;
  return slot;
}

// ---- partitioned form: the directory

inline uint64_t dir_hash(int64_t ns) { return mix_hash((uint64_t)ns, 1); }

// Index of the namespace's table, -1 where it has none.
inline int32_t dir_find(const SlotMap* m, int64_t ns) {
  uint64_t mask = (uint64_t)m->dir_size - 1;
  for (uint64_t b = dir_hash(ns) & mask;; b = (b + 1) & mask) {
    int32_t t = m->dir[b];
    if (t < 0 || m->tabs[t].ns == ns) return t;
  }
}

void dir_insert(SlotMap* m, int64_t ns, int32_t t) {
  if ((m->tabs_live + 1) * 2 >= m->dir_size) {
    m->dir_size *= 2;
    m->dir = (int32_t*)realloc(m->dir, sizeof(int32_t) * m->dir_size);
    memset(m->dir, 0xff, sizeof(int32_t) * m->dir_size);
    uint64_t mask = (uint64_t)m->dir_size - 1;
    // every live table but t (not entered yet; its slot in tabs is set)
    for (int64_t i = 0; i < m->tabs_top; i++) {
      if (m->tabs[i].n < 0 || i == t) continue;
      uint64_t b = dir_hash(m->tabs[i].ns) & mask;
      while (m->dir[b] >= 0) b = (b + 1) & mask;
      m->dir[b] = (int32_t)i;
    }
  }
  uint64_t mask = (uint64_t)m->dir_size - 1;
  uint64_t b = dir_hash(ns) & mask;
  while (m->dir[b] >= 0) b = (b + 1) & mask;
  m->dir[b] = t;
}

void dir_remove(SlotMap* m, int64_t ns) {
  uint64_t mask = (uint64_t)m->dir_size - 1;
  uint64_t hole = dir_hash(ns) & mask;
  while (m->tabs[m->dir[hole]].ns != ns) hole = (hole + 1) & mask;
  int32_t* dir = m->dir;
  dir[close_hole(
      hole, mask, [dir](uint64_t j) { return dir[j] < 0; },
      [m, dir, mask](uint64_t j) {
        return dir_hash(m->tabs[dir[j]].ns) & mask;
      },
      [dir](uint64_t to, uint64_t from) { dir[to] = dir[from]; })] = -1;
}

// ---- partitioned form: one namespace's table

inline uint64_t key_hash(int64_t k) { return mix_hash((uint64_t)k, 0); }

NsBlock block_alloc(int64_t cap) {
  NsBlock b;
  b.cap = cap;
  b.keys = (int64_t*)malloc((sizeof(int64_t) + sizeof(int32_t)) * cap);
  b.slots = (int32_t*)(b.keys + cap);
  b.nb = cap > TINY_CAP ? cap * 2 : 0;
  b.buckets = b.nb ? (NsBucket*)calloc(b.nb, sizeof(NsBucket)) : nullptr;
  return b;
}

inline void block_free(NsBlock* b) {
  free(b->keys);
  free(b->buckets);
}

// Enter (key, slot), known to be absent, into the buckets.
inline void bucket_put(NsBlock* b, int64_t key, int32_t slot) {
  uint64_t mask = (uint64_t)b->nb - 1;
  uint64_t i = key_hash(key) & mask;
  while (b->buckets[i].slot) i = (i + 1) & mask;
  b->buckets[i].key = key;
  b->buckets[i].slot = slot;
}

// Double a full table: the pairs move in their order, the buckets are
// filled again from them.
void table_grow(NsTable* t) {
  NsBlock b = block_alloc(t->mem.cap * 2);
  memcpy(b.keys, t->mem.keys, sizeof(int64_t) * t->n);
  memcpy(b.slots, t->mem.slots, sizeof(int32_t) * t->n);
  if (b.nb)
    for (int64_t i = 0; i < t->n; i++) bucket_put(&b, b.keys[i], b.slots[i]);
  block_free(&t->mem);
  t->mem = b;
}

// A table for a namespace that has none: in the memory of a dropped one
// where one is kept, at the size that one closed at — a window job's
// slices are as large as the slice before them, and a table that is
// neither grown nor paged in while a batch runs is what makes its probe
// cheap. Else new and small, grown by doubling: a size is only ever taken
// on credit of a table that left, so many small namespaces opened after a
// big one was dropped cost what they hold.
int32_t table_open(SlotMap* m, int64_t ns) {
  int32_t ti;
  if (m->tab_free_top) {
    ti = m->tab_free[--m->tab_free_top];
  } else {
    if (m->tabs_top == m->tabs_cap) {
      m->tabs_cap = m->tabs_cap ? m->tabs_cap * 2 : 16;
      m->tabs = (NsTable*)realloc(m->tabs, sizeof(NsTable) * m->tabs_cap);
      m->tab_free =
          (int32_t*)realloc(m->tab_free, sizeof(int32_t) * m->tabs_cap);
    }
    ti = (int32_t)m->tabs_top++;
  }
  NsTable* t = &m->tabs[ti];
  t->ns = ns;
  t->gen = m->next_gen++;
  t->n = 0;
  t->thinned = false;
  t->mem = m->pool_n ? m->pool[--m->pool_n] : block_alloc(FIRST_CAP);
  dir_insert(m, ns, ti);
  m->tabs_live++;
  return ti;
}

// Empty a block's buckets for its next table: the ones its n keys sit in
// where they are few against the buckets, else all of them.
void block_clear(NsBlock* b, int64_t n) {
  if (!b->nb) return;
  if (n * 64 >= b->nb) {
    memset(b->buckets, 0, sizeof(NsBucket) * b->nb);
    return;
  }
  uint64_t mask = (uint64_t)b->nb - 1;
  // find them all first (a bucket emptied early would cut a chain short),
  // each key's place kept where the key was
  for (int64_t i = 0; i < n; i++) {
    uint64_t p = key_hash(b->keys[i]) & mask;
    while (b->buckets[p].key != b->keys[i] || !b->buckets[p].slot)
      p = (p + 1) & mask;
    b->keys[i] = (int64_t)p;
  }
  for (int64_t i = 0; i < n; i++) b->buckets[b->keys[i]].slot = 0;
}

// The table leaves the directory; its slots are the caller's to release.
void table_close(SlotMap* m, int32_t ti) {
  NsTable* t = &m->tabs[ti];
  dir_remove(m, t->ns);
  if (t->mem.nb && m->pool_n < POOL_MAX) {
    block_clear(&t->mem, t->n);
    m->pool[m->pool_n++] = t->mem;
  } else {
    block_free(&t->mem);
  }
  t->n = -1;
  m->tab_free[m->tab_free_top++] = ti;
  m->tabs_live--;
}

// The probe of one key in its namespace's table from its hash: the pair's
// slot, a free one taken where the pair is new (*is_new). -1: full.
inline int32_t part_probe_or_insert(SlotMap* m, NsTable* t, int64_t k,
                                    uint64_t hash, int32_t* grows,
                                    bool* is_new) {
  NsBlock* b = &t->mem;
  *is_new = false;
  NsBucket* empty = nullptr;  // where the key goes if it is new
  if (b->nb) {
    uint64_t mask = (uint64_t)b->nb - 1;
    for (uint64_t i = hash & mask;; i = (i + 1) & mask) {
      NsBucket* e = &b->buckets[i];
      if (!e->slot) {
        empty = e;
        break;
      }
      if (e->key == k) return e->slot;
    }
  } else {
    for (int64_t i = 0; i < t->n; i++)
      if (b->keys[i] == k) return b->slots[i];
  }
  int32_t slot = take_slot(m, k, t->ns, grows);
  if (slot < 0) return -1;
  if (t->n == b->cap) {
    table_grow(t);
    empty = nullptr;
  }
  if (empty) {
    empty->key = k;
    empty->slot = slot;
  } else if (b->nb) {
    bucket_put(b, k, slot);
  }
  b->keys[t->n] = k;
  b->slots[t->n] = slot;
  t->n++;
  *is_new = true;
  return slot;
}

inline int32_t part_find(const NsTable* t, int64_t k, uint64_t hash) {
  const NsBlock* b = &t->mem;
  if (b->nb) {
    uint64_t mask = (uint64_t)b->nb - 1;
    for (uint64_t i = hash & mask;; i = (i + 1) & mask) {
      const NsBucket* e = &b->buckets[i];
      if (!e->slot) return -1;
      if (e->key == k) return e->slot;
    }
  }
  for (int64_t i = 0; i < t->n; i++)
    if (b->keys[i] == k) return b->slots[i];
  return -1;
}

inline void part_prefetch(const NsTable* t, uint64_t hash) {
  if (t->mem.nb)
    __builtin_prefetch(&t->mem.buckets[hash & ((uint64_t)t->mem.nb - 1)], 0,
                       1);
}

// Take one key out of its table's buckets (backward shift inside the
// table); its slot, or -1. keys[] / slots[] keep the pair until
// table_close_gaps.
inline int32_t part_erase(const SlotMap* m, NsTable* t, int64_t k,
                          uint64_t hash) {
  NsBlock* b = &t->mem;
  if (!b->nb) {
    // scanned table: a pair erased twice in one call is still in keys[]
    for (int64_t i = 0; i < t->n; i++)
      if (b->keys[i] == k && m->slot_used[b->slots[i]]) return b->slots[i];
    return -1;
  }
  uint64_t mask = (uint64_t)b->nb - 1;
  uint64_t i = hash & mask;
  for (;; i = (i + 1) & mask) {
    if (!b->buckets[i].slot) return -1;
    if (b->buckets[i].key == k) break;
  }
  int32_t slot = b->buckets[i].slot;
  NsBucket* bk = b->buckets;
  bk[close_hole(
      i, mask, [bk](uint64_t j) { return !bk[j].slot; },
      [bk, mask](uint64_t j) { return key_hash(bk[j].key) & mask; },
      [bk](uint64_t to, uint64_t from) { bk[to] = bk[from]; })].slot = 0;
  return slot;
}

// keys[] / slots[] dense again after part_erase: the pairs whose slot was
// released go, the others keep their order.
void table_close_gaps(const SlotMap* m, NsTable* t) {
  NsBlock* b = &t->mem;
  int64_t kept = 0;
  for (int64_t i = 0; i < t->n; i++) {
    if (!m->slot_used[b->slots[i]]) continue;
    b->keys[kept] = b->keys[i];
    b->slots[kept++] = b->slots[i];
  }
  t->n = kept;
  t->thinned = false;
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
  // what the matrix holds of each column's namespace (the last call's)
  int64_t* ends;         // [k] the namespace
  uint64_t* gens;        // [k] its table's gen
  int64_t* consumed;     // [k] pairs of its keys[] / slots[] entered
  uint64_t thinned;      // the map's count of gap-closing erases then
  int64_t width;         // columns the last copy-out wrote (tried first)
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
  int32_t* bk = c->buckets;
  bk[close_hole(
      carry_bucket_of(c, c->keys[r]), mask,
      [bk](uint64_t j) { return bk[j] == -1; },
      [c, bk, mask](uint64_t j) {
        return mix_hash((uint64_t)c->keys[bk[j]], 0) & mask;
      },
      [bk](uint64_t to, uint64_t from) { bk[to] = bk[from]; })] = -1;
  int64_t last = --c->rows;
  if (r == last) return;
  c->buckets[carry_bucket_of(c, c->keys[last])] = (int32_t)r;
  c->keys[r] = c->keys[last];
  memcpy(c->mat + r * c->k, c->mat + last * c->k, sizeof(int32_t) * c->k);
}

// Columns of the matrix a fire is handed where its fullest row holds
// ``fullest`` live cells of k: the next power of two, at least 2 (so a
// stream's first one-slice windows take the two-column program too) and
// at most k. The Python index has the same rule (``fire_matrix_width``,
// state/slot_table.py); a test holds the two equal.
inline int64_t fire_width(int64_t k, int64_t fullest) {
  int64_t w = 2;
  while (w < fullest) w <<= 1;
  return w < k ? w : k;
}

// Live cells of each row of ``mat`` ([rows, k]) packed to the row's left
// in ``out`` at ``width`` < k columns to the row, in the order of their
// columns, zeros behind them; returns the fullest row's count, at once
// where a row holds more than ``width`` (what is written is then of no
// use). No branch on a cell: each is stored where the next live one goes
// and stays if it is live; the store past a full row lands in the next
// row's place, or in the k - width columns ``out`` holds beyond the last.
// K, W: k and width where the compiler is to know them (0: it is not).
template <int K, int W>
int64_t carry_pack_rows(const int32_t* __restrict mat, int64_t rows,
                        int64_t k_, int64_t width_,
                        int32_t* __restrict out) {
  const int64_t k = K ? K : k_, width = W ? W : width_;
  int64_t fullest = 0;
  for (int64_t r = 0; r < rows && fullest <= width; r++) {
    const int32_t* row = mat + r * k;
    int32_t* dst = out + r * width;
    for (int64_t j = 0; j < width; j++) dst[j] = 0;
    int64_t n = 0;
    for (int64_t j = 0; j < k; j++) {
      const int32_t slot = row[j];
      dst[n] = slot;
      n += slot != 0;
    }
    if (n > fullest) fullest = n;
  }
  return fullest;
}

// The same for any (k, width): windows of up to eight slices get the loop
// unrolled for theirs — 0.28 ms for 149,000 rows of 5 where the
// runtime-sized loop takes 0.95 and a memcpy of them 0.22 (one core of
// the build sandbox) — any other the runtime-sized one.
int64_t carry_pack(const int32_t* mat, int64_t rows, int64_t k,
                   int64_t width, int32_t* out) {
  switch (k * 16 + width) {  // width < k is 2 or 4 here: no two cases meet
#define PACK(K, W)     \
  case K * 16 + W:     \
    return carry_pack_rows<K, W>(mat, rows, k, width, out);
    PACK(3, 2) PACK(4, 2) PACK(5, 2) PACK(6, 2) PACK(7, 2) PACK(8, 2)
    PACK(5, 4) PACK(6, 4) PACK(7, 4) PACK(8, 4)
#undef PACK
    default:
      return carry_pack_rows<0, 0>(mat, rows, k, width, out);
  }
}

// The carry's matrix as the fire gets it: each row's live cells in its
// first columns, in the order of their slices, zeros behind them, in
// ``out`` at fire_width(k, fullest row) columns to the row; returns that
// width. The fire merges a row's cells with a commutative reduction and
// slot 0 holds the identity, so which column a cell stands in means
// nothing to it, and a column no row fills is not gathered at all. Where
// that width is k (a row holding all k cells is met: among the first few
// under keys that live all run) the matrix goes out as it is. The width
// the last call found is tried first, so a steady stream is packed in
// one pass; whatever it was, what is written depends on the matrix alone.
int64_t carry_copy_out(SliceCarry* c, int32_t* out) {
  const int64_t k = c->k, rows = c->rows;
  int64_t full = 1;  // the fewest live cells under which a row takes k
  while (fire_width(k, full) < k) full++;
  int64_t width = c->width > 0 && c->width < k ? c->width : k;
  for (;;) {
    int64_t fullest = 0;
    if (width < k) {
      fullest = carry_pack(c->mat, rows, k, width, out);
    } else {
      for (int64_t r = 0; r < rows && fullest < full; r++) {
        const int32_t* row = c->mat + r * k;
        int64_t n = 0;
        for (int64_t j = 0; j < k; j++) n += row[j] != 0;
        if (n > fullest) fullest = n;
      }
    }
    const int64_t want = fire_width(k, fullest);
    if (want == width) break;
    width = want;
  }
  if (width >= k) memcpy(out, c->mat, sizeof(int32_t) * rows * k);
  c->width = width;
  return width;
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
    w->fresh = (int64_t*)realloc(w->fresh, sizeof(int64_t) * cap);
    w->order = (int32_t*)realloc(w->order, sizeof(int32_t) * cap);
    w->table = (int32_t*)realloc(w->table, sizeof(int32_t) * cap);
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

// Pass A of the batch sweeps: reads ``vals`` alone and changes nothing but
// the scratch. Each record's distinct namespace (first-seen index, in
// w->sinv) and the records under each (w->count), the first-seen indexes
// by ascending namespace in w->order, w->fresh zeroed; *k = distinct
// namespaces. ``vals`` are timestamps where width > 0: a record's
// namespace is then the end of its slice, ts - floormod(ts - offset,
// width) + width (the assigner's rule; an in-order run stays in the slice
// of the record before it, so a compare stands in for the division).
// Where width == 0 they are the namespaces themselves. False where there
// are more than max_uniq distinct namespaces, or (width > 0) a slice end
// lies below live_from: a late record, which the caller's own path drops
// and counts.
inline bool sweep_pass_a(SweepScratch* w, int64_t n, const int64_t* vals,
                         int64_t offset, int64_t width, int64_t live_from,
                         int64_t max_uniq, int64_t* out_k) {
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
        if (ns < live_from) return false;
      } else {
        cur_val = v;
      }
      cur = sweep_index_of(w, ns, &k, max_uniq);
      if (cur < 0) return false;
    }
    sinv[r] = cur;
    run++;
  }
  if (cur >= 0) w->count[cur] += run;
  for (int64_t j = 0; j < k; j++) {
    w->order[j] = (int32_t)j;
    w->fresh[j] = 0;
  }
  std::sort(w->order, w->order + k,
            [w](int32_t a, int32_t b) { return w->val[a] < w->val[b]; });
  *out_k = k;
  return true;
}

// ---- a batch's rows grouped by key (sm_group_rows)

// one bucket of the grouping pass's batch-local table (16 bytes)
struct RowGroup {
  int64_t key;
  uint32_t stamp;  // the call that wrote it; any other reads as empty
  int32_t group;
};

// The pass's table, kept per thread from call to call: the stamp tells
// this call's entries from older ones, so nothing is cleared per call.
struct RowGroupScratch {
  RowGroup* tab = nullptr;
  uint64_t size = 0;  // power of two, >= 4 * the call's groups
  uint32_t stamp = 0;
  ~RowGroupScratch() { free(tab); }
};

thread_local RowGroupScratch row_groups;

// fourfold; this call's entries move over, older ones are left behind
void row_groups_grow(RowGroupScratch* w) {
  RowGroup* old = w->tab;
  const uint64_t old_size = w->size;
  w->size = old_size * 4;
  w->tab = (RowGroup*)calloc(w->size, sizeof(RowGroup));
  const uint64_t mask = w->size - 1;
  for (uint64_t j = 0; j < old_size; j++) {
    if (old[j].stamp != w->stamp) continue;
    uint64_t b = key_hash(old[j].key) & mask;
    while (w->tab[b].stamp == w->stamp) b = (b + 1) & mask;
    w->tab[b] = old[j];
  }
  free(old);
}

}  // namespace

extern "C" {

void* sm_create(int64_t initial_capacity, int64_t max_capacity,
                int32_t partitioned) {
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
  m->partitioned = partitioned != 0;
  if (!m->partitioned) flat_build_buckets(m);
  m->dir_size = 64;
  m->dir = (int32_t*)malloc(sizeof(int32_t) * m->dir_size);
  memset(m->dir, 0xff, sizeof(int32_t) * m->dir_size);
  return m;
}

void sm_destroy(void* h) {
  SlotMap* m = (SlotMap*)h;
  for (int64_t i = 0; i < m->tabs_top; i++)
    if (m->tabs[i].n >= 0) block_free(&m->tabs[i].mem);
  for (int i = 0; i < m->pool_n; i++) block_free(&m->pool[i]);
  free(m->tabs);
  free(m->tab_free);
  free(m->dir);
  free(m->thin_list);
  free(m->buckets);
  free(m->slot_key);
  free(m->slot_ns);
  free(m->slot_used);
  free(m->free_stack);
  free(m->sweep.sinv);
  free(m->sweep.val);
  free(m->sweep.count);
  free(m->sweep.fresh);
  free(m->sweep.order);
  free(m->sweep.table);
  free(m->sweep.tab);
  free(m->sweep.cell);
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
  // Chunked software prefetch: the tables span far more than L2, so a
  // probe's first bucket (and, flat, the slot_key/slot_ns verify behind
  // it) is a likely cache miss. Hash a chunk up front, prefetch every home
  // bucket line, then (flat) peek the now warm buckets to prefetch the
  // slot rows. Inserts during processing only make earlier hints stale —
  // hints are never required for correctness.
  constexpr int64_t CHUNK = 256;
  uint64_t hashes[CHUNK];
  int32_t tis[CHUNK];
  for (int64_t base = 0; base < n; base += CHUNK) {
    int64_t end = base + CHUNK < n ? base + CHUNK : n;
    bool is_new;
    if (m->partitioned) {
      // each record's table first (opened where its namespace has none:
      // the table array may move, so indexes and not pointers)
      int32_t ti = -1;
      for (int64_t r = base; r < end; r++) {
        if (ti < 0 || m->tabs[ti].ns != nss[r]) {
          ti = dir_find(m, nss[r]);
          if (ti < 0) ti = table_open(m, nss[r]);
        }
        tis[r - base] = ti;
      }
      for (int64_t r = base; r < end; r++) {
        hashes[r - base] = key_hash(keys[r]);
        part_prefetch(&m->tabs[tis[r - base]], hashes[r - base]);
      }
      int32_t slot = 0;
      int64_t r = base;
      for (; r < end && slot >= 0; r++) {
        slot = part_probe_or_insert(m, &m->tabs[tis[r - base]], keys[r],
                                    hashes[r - base], &grows, &is_new);
        out_slots[r] = slot;
        if (out_is_new) out_is_new[r] = is_new;
      }
      if (slot < 0) {
        // full: the tables this chunk opened and nothing entered go again
        for (r = base; r < end; r++)
          if (m->tabs[tis[r - base]].n == 0) table_close(m, tis[r - base]);
        return -1;
      }
      continue;
    }
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
      int32_t slot = flat_probe_or_insert(m, keys[r], nss[r],
                                          hashes[r - base], &grows, &is_new);
      if (slot < 0) return -1;
      out_slots[r] = slot;
      if (out_is_new) out_is_new[r] = is_new;
    }
  }
  return grows;
}

// One sweep that resolves a whole batch: (key, namespace) -> slot for
// every record, and per distinct namespace the records under it and the
// pairs newly given a slot.
//
// Pass A (sweep_pass_a above: what ``vals`` are, and when it gives a
// batch up) changes nothing, and where it gives up the call returns -2
// with the index untouched. Pass B is
// sm_lookup_or_insert's probe (same hashes, prefetch, growth); partitioned,
// each distinct namespace's table is found (or opened) once before it and
// a record goes straight to its own slice's table, where its new pair is
// appended: the namespace's slots stay in record order.
//
// out_groups is [3, max_uniq] int64: the distinct namespaces ascending,
// the records under each, the new pairs of each. *out_k = distinct
// namespaces. Returns grows (>= 0) or -1 (full at max_capacity;
// out_groups then counts what was inserted before it).
int32_t sm_resolve_grouped(void* h, int64_t n, const int64_t* keys,
                           const int64_t* vals, int64_t offset,
                           int64_t width, int64_t live_from,
                           int64_t max_uniq, int32_t* out_slots,
                           int64_t* out_groups, int64_t* out_k) {
  SlotMap* m = (SlotMap*)h;
  SweepScratch* w = &m->sweep;
  int64_t k;
  if (!sweep_pass_a(w, n, vals, offset, width, live_from, max_uniq, &k))
    return -2;
  *out_k = k;
  const int32_t* sinv = w->sinv;
  // ---- pass B: the probe (sm_lookup_or_insert's, see there)
  int32_t grows = 0;
  bool full = false;
  constexpr int64_t CHUNK = 256;
  uint64_t hashes[CHUNK];
  if (m->partitioned) {
    // ascending, so that new tables open in the order of their names
    for (int64_t j = 0; j < k; j++) {
      int32_t u = w->order[j];
      int32_t ti = dir_find(m, w->val[u]);
      w->table[u] = ti >= 0 ? ti : table_open(m, w->val[u]);
    }
    NsTable* tabs = m->tabs;  // no table is opened from here on
    for (int64_t base = 0; base < n && !full; base += CHUNK) {
      int64_t end = base + CHUNK < n ? base + CHUNK : n;
      for (int64_t r = base; r < end; r++) {
        hashes[r - base] = key_hash(keys[r]);
        part_prefetch(&tabs[w->table[sinv[r]]], hashes[r - base]);
      }
      for (int64_t r = base; r < end; r++) {
        int32_t u = sinv[r];
        bool is_new;
        int32_t slot = part_probe_or_insert(m, &tabs[w->table[u]], keys[r],
                                            hashes[r - base], &grows,
                                            &is_new);
        if (slot < 0) {
          full = true;
          break;
        }
        out_slots[r] = slot;
        w->fresh[u] += is_new;
      }
    }
    if (full)
      for (int64_t u = 0; u < k; u++)
        if (tabs[w->table[u]].n == 0) table_close(m, w->table[u]);
  } else {
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
        int32_t slot = flat_probe_or_insert(m, keys[r], w->val[u],
                                            hashes[r - base], &grows,
                                            &is_new);
        if (slot < 0) {
          full = true;
          break;
        }
        out_slots[r] = slot;
        w->fresh[u] += is_new;
      }
    }
  }
  for (int64_t j = 0; j < k; j++) {
    int32_t u = w->order[j];
    out_groups[j] = w->val[u];
    out_groups[max_uniq + j] = w->count[u];
    out_groups[2 * max_uniq + j] = w->fresh[u];
  }
  return full ? -1 : grows;
}

// The same sweep over a batch whose records belong to P maps, one per
// shard of a mesh: record r goes to handles[shard(r)], where shard(r) =
// group_shard[murmur_fmix32(fold(key)) % max_parallelism] — the key-group
// routing (state/keygroups.py assign_key_groups) through the caller's
// group -> shard table, -1 where a group has no shard here. Each map sees
// its own records in record order, only interleaved with the others', so
// its tables, free stack and slot ids come out as from sm_resolve_grouped
// over its records alone: a (namespace, shard) cell's table is found or
// opened only where the cell holds a record, per shard in ascending order
// of the namespaces.
//
// Nothing is changed before pass A and the routing are through: -2 where
// pass A gives the batch up, a record's group has no shard, or a map is
// not partitioned.
//
// out_shards / out_slots are per record, in record order. ``dirty``,
// where given, is the owner's [P, dirty_stride] byte map of slots touched:
// a record's slot is marked in its shard's row (a slot at or past the
// stride, handed out by a growth inside this call, is the owner's to
// mark once it has grown its map). out_groups is [2, max_uniq] int64: the
// distinct namespaces ascending and the records under each. out_per_shard
// is [2, P] int64: per shard its new pairs and its grows (-1: full at
// max_capacity). Returns 0, or -1 where some shard was full: the
// sweep stops at that record, and out_per_shard counts what was inserted
// before it.
int32_t sm_resolve_grouped_sharded(
    void* const* handles, int64_t P, int64_t n, const int64_t* keys,
    const int64_t* vals, const int32_t* group_shard, int64_t max_parallelism,
    int64_t offset, int64_t width, int64_t live_from, int64_t max_uniq,
    uint8_t* dirty, int64_t dirty_stride, int32_t* out_shards,
    int32_t* out_slots, int64_t* out_groups, int64_t* out_k,
    int64_t* out_per_shard) {
  for (int64_t p = 0; p < P; p++)
    if (!((SlotMap*)handles[p])->partitioned) return -2;
  SweepScratch* w = &((SlotMap*)handles[0])->sweep;
  int64_t k;
  if (!sweep_pass_a(w, n, vals, offset, width, live_from, max_uniq, &k))
    return -2;
  const int32_t* sinv = w->sinv;
  if (k * P > w->cell_cap) {
    free(w->cell);
    w->cell_cap = k * P;
    w->cell = (int32_t*)malloc(sizeof(int32_t) * w->cell_cap);
  }
  int32_t* cell = w->cell;
  memset(cell, 0, sizeof(int32_t) * k * P);
  // ---- routing: each record's shard, the cells that hold a record. x % d
  // for 32-bit x by two multiplications (Lemire's fastmod, exact)
  const uint64_t d = (uint64_t)max_parallelism;
  const uint64_t fast = UINT64_C(0xFFFFFFFFFFFFFFFF) / d + 1;
  for (int64_t r = 0; r < n; r++) {
    uint64_t key = (uint64_t)keys[r];
    // the arithmetic shift of the signed key, as NumPy's k >> 32
    uint32_t x = (uint32_t)(key ^ (uint64_t)(keys[r] >> 32));
    x ^= x >> 16;
    x *= 0x85EBCA6Bu;
    x ^= x >> 13;
    x *= 0xC2B2AE35u;
    x ^= x >> 16;
    uint64_t group =
        (uint64_t)(((unsigned __int128)(fast * x) * d) >> 64);
    int32_t s = group_shard[group];
    if (s < 0 || s >= P) return -2;
    out_shards[r] = s;
    cell[(int64_t)sinv[r] * P + s] = 1;
  }
  *out_k = k;
  int64_t* fresh = out_per_shard;
  int64_t* grows = out_per_shard + P;
  // ---- the cells' tables: per shard ascending, so that new tables open
  // in the order of their names
  for (int64_t p = 0; p < P; p++) {
    SlotMap* m = (SlotMap*)handles[p];
    fresh[p] = grows[p] = 0;
    for (int64_t j = 0; j < k; j++) {
      int32_t u = w->order[j];
      int32_t* c = &cell[(int64_t)u * P + p];
      if (!*c) {
        *c = -1;
        continue;
      }
      int32_t ti = dir_find(m, w->val[u]);
      *c = ti >= 0 ? ti : table_open(m, w->val[u]);
    }
  }
  // ---- pass B: the probe, each record in its own shard's map (no table
  // is opened from here on; a map's growth moves its slot arrays alone)
  bool full = false;
  constexpr int64_t CHUNK = 256;
  uint64_t hashes[CHUNK];
  NsTable* tables[CHUNK];
  for (int64_t base = 0; base < n && !full; base += CHUNK) {
    int64_t end = base + CHUNK < n ? base + CHUNK : n;
    for (int64_t r = base; r < end; r++) {
      int32_t s = out_shards[r];
      NsTable* t = &((SlotMap*)handles[s])
                        ->tabs[cell[(int64_t)sinv[r] * P + s]];
      tables[r - base] = t;
      hashes[r - base] = key_hash(keys[r]);
      part_prefetch(t, hashes[r - base]);
    }
    int64_t r = base;
    for (; r < end; r++) {
      int32_t s = out_shards[r];
      bool is_new;
      int32_t grew = 0;
      int32_t slot = part_probe_or_insert((SlotMap*)handles[s],
                                          tables[r - base], keys[r],
                                          hashes[r - base], &grew, &is_new);
      if (slot < 0) {
        grows[s] = -1;
        full = true;
        break;
      }
      out_slots[r] = slot;
      fresh[s] += is_new;
      grows[s] += grew;
      // the mark's cache line is asked for now and written after the
      // chunk: the map is far larger than the cache, and a store that
      // waits for its line holds the probes behind it back
      if (dirty && slot < dirty_stride)
        __builtin_prefetch(&dirty[s * dirty_stride + slot], 1, 1);
    }
    if (dirty)
      for (int64_t q = base; q < r; q++)
        if (out_slots[q] < dirty_stride)
          dirty[out_shards[q] * dirty_stride + out_slots[q]] = 1;
  }
  if (full)
    for (int64_t p = 0; p < P; p++) {
      SlotMap* m = (SlotMap*)handles[p];
      for (int64_t u = 0; u < k; u++) {
        int32_t ti = cell[u * P + p];
        if (ti >= 0 && m->tabs[ti].n == 0) table_close(m, ti);
      }
    }
  for (int64_t j = 0; j < k; j++) {
    int32_t u = w->order[j];
    out_groups[j] = w->val[u];
    out_groups[max_uniq + j] = w->count[u];
  }
  return full ? -1 : 0;
}

// Read-only batch probe: out_slots[i] = slot id, or -1 if the pair is not
// present. Never inserts — this is the queryable-state point-lookup path
// (the role of the reference's QueryableStateClient -> KvStateServer
// lookups against the live backend).
void sm_lookup(void* h, int64_t n, const int64_t* keys, const int64_t* nss,
               int32_t* out_slots) {
  SlotMap* m = (SlotMap*)h;
  constexpr int64_t CHUNK = 256;
  uint64_t hashes[CHUNK];
  int32_t tis[CHUNK];
  for (int64_t base = 0; base < n; base += CHUNK) {
    int64_t end = base + CHUNK < n ? base + CHUNK : n;
    if (m->partitioned) {
      int32_t ti = -1;
      for (int64_t r = base; r < end; r++) {
        if (ti < 0 || m->tabs[ti].ns != nss[r]) ti = dir_find(m, nss[r]);
        tis[r - base] = ti;
        hashes[r - base] = key_hash(keys[r]);
        if (ti >= 0) part_prefetch(&m->tabs[ti], hashes[r - base]);
      }
      for (int64_t r = base; r < end; r++)
        out_slots[r] = tis[r - base] < 0
                           ? -1
                           : part_find(&m->tabs[tis[r - base]], keys[r],
                                       hashes[r - base]);
      continue;
    }
    uint64_t mask = (uint64_t)m->bucket_count - 1;
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
    for (int64_t r = base; r < end; r++)
      out_slots[r] = flat_find(m, keys[r], nss[r], hashes[r - base]);
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

// Erase pairs one by one (TTL expiry, paged eviction, fired sessions);
// writes freed slot ids to out_slots (only for pairs that were present).
// Returns the number actually erased. Partitioned: each key leaves its
// namespace's table, then the tables touched close the gaps in their
// keys[] / slots[] (one pass each) and a table left empty is dropped; the
// count of such calls tells the carried fire matrix that what it consumed
// of a namespace may have moved.
int64_t sm_erase(void* h, int64_t n, const int64_t* keys, const int64_t* nss,
                 int32_t* out_slots) {
  SlotMap* m = (SlotMap*)h;
  int64_t erased = 0;
  constexpr int64_t CHUNK = 256;
  uint64_t hashes[CHUNK];
  if (m->partitioned) {
    int64_t thinned = 0;
    int32_t ti = -1;
    for (int64_t r = 0; r < n; r++) {
      if (ti < 0 || m->tabs[ti].ns != nss[r]) ti = dir_find(m, nss[r]);
      if (ti < 0) continue;
      NsTable* t = &m->tabs[ti];
      int32_t slot = part_erase(m, t, keys[r], key_hash(keys[r]));
      if (slot < 0) continue;
      release_slot(m, slot);
      out_slots[erased++] = slot;
      if (t->thinned) continue;
      t->thinned = true;
      if (thinned == m->thin_cap) {
        m->thin_cap = m->thin_cap ? m->thin_cap * 2 : 64;
        m->thin_list =
            (int32_t*)realloc(m->thin_list, sizeof(int32_t) * m->thin_cap);
      }
      m->thin_list[thinned++] = ti;
    }
    for (int64_t i = 0; i < thinned; i++) {
      NsTable* t = &m->tabs[m->thin_list[i]];
      table_close_gaps(m, t);
      if (t->n == 0) table_close(m, m->thin_list[i]);
    }
    if (thinned) m->thinned++;
    return erased;
  }
  uint64_t mask = (uint64_t)m->bucket_count - 1;
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
      int32_t slot = flat_erase(m, keys[r], nss[r], hashes[r - base]);
      if (slot < 0) continue;
      release_slot(m, slot);
      out_slots[erased++] = slot;
    }
  }
  return erased;
}

// ---- a namespace as a whole (partitioned; a flat map holds no table and
// answers 0 to each)

// The retire: every pair of the namespaces given leaves with its table.
// Their slots are written to out_slots (namespace by namespace as given,
// insertion order within one) and released; each table goes to the pool.
// No hash, no gather, no shift per pair. Returns the slots written.
int64_t sm_drop_namespaces(void* h, int64_t n, const int64_t* nss,
                           int32_t* out_slots) {
  SlotMap* m = (SlotMap*)h;
  int64_t total = 0;
  for (int64_t i = 0; i < n; i++) {
    int32_t ti = dir_find(m, nss[i]);
    if (ti < 0) continue;
    const NsTable* t = &m->tabs[ti];
    memcpy(out_slots + total, t->mem.slots, sizeof(int32_t) * t->n);
    for (int64_t j = 0; j < t->n; j++) release_slot(m, t->mem.slots[j]);
    total += t->n;
    table_close(m, ti);
  }
  return total;
}

int64_t sm_namespace_count(void* h) { return ((SlotMap*)h)->tabs_live; }

// The live namespaces, in the order their tables were opened.
void sm_namespaces(void* h, int64_t* out) {
  SlotMap* m = (SlotMap*)h;
  int64_t k = 0;
  for (int64_t i = 0; i < m->tabs_top; i++)
    if (m->tabs[i].n >= 0) out[k++] = i;
  std::sort(out, out + k, [m](int64_t a, int64_t b) {
    return m->tabs[a].gen < m->tabs[b].gen;
  });
  for (int64_t i = 0; i < k; i++) out[i] = m->tabs[out[i]].ns;
}

// A namespace's slots in insertion order: the count, and the first ``cap``
// of them copied to out.
int64_t sm_namespace_slots(void* h, int64_t ns, int32_t* out, int64_t cap) {
  SlotMap* m = (SlotMap*)h;
  int32_t ti = dir_find(m, ns);
  if (ti < 0) return 0;
  const NsTable* t = &m->tabs[ti];
  memcpy(out, t->mem.slots, sizeof(int32_t) * (t->n < cap ? t->n : cap));
  return t->n;
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
  int32_t rc = 0;
  // partitioned: the one table of namespace 0 (no other is opened here,
  // so the pointer holds)
  NsTable* t = nullptr;
  if (m->partitioned && n) {
    int32_t ti = dir_find(m, 0);
    if (ti < 0) ti = table_open(m, 0);
    t = &m->tabs[ti];
  }
  constexpr int64_t CHUNK = 256;
  uint64_t hashes[CHUNK];
  for (int64_t base = 0; base < n && rc == 0; base += CHUNK) {
    int64_t end = base + CHUNK < n ? base + CHUNK : n;
    uint64_t pmask = (uint64_t)m->bucket_count - 1;
    for (int64_t r = base; r < end; r++) {
      uint64_t hh = key_hash(keys[r]);
      hashes[r - base] = hh;
      if (t)
        part_prefetch(t, hh);
      else
        __builtin_prefetch(&m->buckets[hh & pmask], 0, 1);
    }
    if (!t)
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
            rc = -2;
            break;
          }
          se_key[sb] = se;
          se_idx[sb] = (int32_t)k_count;
          out_uniq[k_count++] = se;
          break;
        }
        if (se_key[sb] == se) break;
        sb = (sb + 1) & (nb - 1);
      }
      if (rc) break;
      out_sinv[r] = se_idx[sb];
      // key -> column (lookup-or-insert, ns = 0)
      bool is_new;
      int32_t col =
          t ? part_probe_or_insert(m, t, keys[r], hashes[r - base], &grows,
                                   &is_new)
            : flat_probe_or_insert(m, keys[r], 0, hashes[r - base], &grows,
                                   &is_new);
      if (col < 0) {
        rc = -1;
        break;
      }
      out_cols[r] = col;
      out_is_new[r] = is_new;
      if (col > max_col) max_col = col;
    }
  }
  free(se_key);
  free(se_idx);
  if (t && t->n == 0) table_close(m, (int32_t)(t - m->tabs));
  if (rc) return rc;
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

// One batch's rows grouped by key in one hash pass, with no sort and
// no map handle: each row's group (out_group, numbered in the order the
// keys first appear) and each group's first row (out_first, ascending;
// the caller gives both n places). Returns the groups, or -1 where n
// passes what an int32 group number holds.
int64_t sm_group_rows(int64_t n, const int64_t* keys, int64_t* out_group,
                      int64_t* out_first) {
  if (n > INT32_MAX) return -1;
  RowGroupScratch* w = &row_groups;
  if (!w->tab) {
    w->size = 1024;
    w->tab = (RowGroup*)calloc(w->size, sizeof(RowGroup));
  }
  if (++w->stamp == 0) {  // the stamp came round: forget every entry
    memset(w->tab, 0, sizeof(RowGroup) * w->size);
    w->stamp = 1;
  }
  const uint32_t stamp = w->stamp;
  uint64_t mask = w->size - 1;
  int64_t k = 0;
  for (int64_t i = 0; i < n; i++) {
    const int64_t key = keys[i];
    uint64_t b = key_hash(key) & mask;
    for (;;) {
      RowGroup& e = w->tab[b];
      if (e.stamp != stamp) {  // a key the batch has not met
        if ((uint64_t)(k + 1) * 4 > w->size) {  // a quarter full at most
          row_groups_grow(w);
          mask = w->size - 1;
          b = key_hash(key) & mask;
          continue;
        }
        e.key = key;
        e.stamp = stamp;
        e.group = (int32_t)k;
        out_first[k] = i;
        out_group[i] = k++;
        break;
      }
      if (e.key == key) {
        out_group[i] = e.group;
        break;
      }
      b = (b + 1) & mask;
    }
  }
  return k;
}

// ---- the fire path's carried slot matrix -------------------------------
//
// A window's (keys, [rows, k] slot matrix) kept from one fire to the next
// for a partitioned map. A sliding window shares k-1 of its k slices with
// the window before it, so an advance drops the columns that left, probes
// only the cells that entered into a key -> row table that persists
// between calls, and sweeps out the rows whose last cell left. The cells
// that entered are read where they lie: a namespace's table holds its
// (keys[], slots[]) in insertion order and only ever appends until it is
// dropped, so the pairs past what the matrix consumed of a kept column,
// and all of a new column's, are the cells — no gather through slot_key.
// The same call from an empty carry is the from-nothing rebuild. Rows stay
// dense (an emptied row is overwritten by the last one), so row order is
// arbitrary; the carry's own columns are the caller's slice order (the
// shift depends on it), the matrix handed out is packed (carry_copy_out).

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
  free(c->ends);
  free(c->gens);
  free(c->consumed);
  free(c);
}

// One fire's advance to the window over the k namespaces ``ends``. Where
// they are the last call's moved on by ``shift`` < k, and every kept
// column the matrix holds cells of is still the same table (its gen: a
// namespace dropped and made again is another) with nothing erased from
// any table in between, the shift leftmost columns leave and only the
// cells that entered are probed; anything else starts from nothing.
// Writes the advanced keys to out_keys and the matrix, as the fire gets it
// (carry_copy_out above: live cells packed to the left of each row, cut
// to *out_width columns, rows contiguous at that width), to out_mat, and
// returns the rows written, *out_cells = cells entered, *out_removed =
// rows swept out (their last cell left and nothing that entered brought
// their key back; a matrix started from nothing sweeps none). The out
// arrays hold out_rows rows of k columns; where that is fewer than the
// carry's rows + the cells entering, nothing is changed and -(rows
// needed) is returned. The out arrays are the caller's: the carry never
// touches them again.
int64_t sm_carry_advance(void* h, void* map, int64_t k, const int64_t* ends,
                         int64_t out_rows, int64_t* out_keys,
                         int32_t* out_mat, int64_t* out_cells,
                         int64_t* out_removed, int64_t* out_width) {
  SliceCarry* c = (SliceCarry*)h;
  const SlotMap* m = (const SlotMap*)map;
  int64_t shift = k;
  if (k == c->k && c->thinned == m->thinned)
    for (int64_t s = 0; s < k && shift == k; s++)
      if (!memcmp(c->ends + s, ends, sizeof(int64_t) * (k - s))) shift = s;
  for (int64_t j = 0; j + shift < k; j++) {
    if (!c->consumed[j + shift]) continue;
    int32_t ti = dir_find(m, ends[j]);
    if (ti < 0 || m->tabs[ti].gen != c->gens[j + shift]) shift = k;
  }
  int64_t cells = 0;
  for (int64_t j = 0; j < k; j++) {
    int32_t ti = dir_find(m, ends[j]);
    if (ti >= 0)
      cells += m->tabs[ti].n - (j + shift < k ? c->consumed[j + shift] : 0);
  }
  int64_t bound = (shift < k ? c->rows : 0) + cells;
  if (bound > out_rows) return -bound;
  *out_cells = cells;
  *out_removed = 0;
  int64_t n_emptied = 0;
  if (shift >= k) {
    if (k != c->k) {
      if (c->row_cap && k)
        c->mat = (int32_t*)realloc(c->mat, sizeof(int32_t) * c->row_cap * k);
      c->ends = (int64_t*)realloc(c->ends, sizeof(int64_t) * (k ? k : 1));
      c->gens = (uint64_t*)realloc(c->gens, sizeof(uint64_t) * (k ? k : 1));
      c->consumed =
          (int64_t*)realloc(c->consumed, sizeof(int64_t) * (k ? k : 1));
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
  uint64_t hashes[CHUNK];
  for (int64_t col = 0; col < k; col++) {
    int64_t seen = col + shift < k ? c->consumed[col + shift] : 0;
    int32_t ti = dir_find(m, ends[col]);
    c->ends[col] = ends[col];
    c->gens[col] = ti >= 0 ? m->tabs[ti].gen : 0;
    c->consumed[col] = ti >= 0 ? m->tabs[ti].n : 0;
    if (ti < 0) continue;
    const int64_t* seg_keys = m->tabs[ti].mem.keys + seen;
    const int32_t* seg_slots = m->tabs[ti].mem.slots + seen;
    int64_t n = m->tabs[ti].n - seen;
    for (int64_t base = 0; base < n; base += CHUNK) {
      int64_t end = base + CHUNK < n ? base + CHUNK : n;
      // same prefetch discipline as the probe paths: the key -> row
      // table and the rows it names are each a likely miss
      for (int64_t i = base; i < end; i++) {
        hashes[i - base] = key_hash(seg_keys[i]);
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
        int64_t key = seg_keys[i];
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
  c->thinned = m->thinned;
  // rows still empty go, highest first: whatever lies above the one
  // being judged is live, so the last row may fill its place
  for (int64_t i = 0; i < n_emptied; i++) {
    const int32_t* row = c->mat + (int64_t)c->emptied[i] * k;
    int32_t live = 0;
    for (int64_t j = 0; j < k; j++) live |= row[j];
    if (!live) {
      carry_remove_row(c, c->emptied[i]);
      ++*out_removed;
    }
  }
  memcpy(out_keys, c->keys, sizeof(int64_t) * c->rows);
  *out_width = carry_copy_out(c, out_mat);
  return c->rows;
}

}  // extern "C"
