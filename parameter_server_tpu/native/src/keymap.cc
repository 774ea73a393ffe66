// Native key localization: the persistent key->slot map that is the stateful
// Localizer's hot path, (further down) the one-pass batch localization of
// the stateless maps on the worker, and (last) a server's one-pass
// localization of a request's leg against its shard map.
//
// The reference keeps the streaming-key vocabulary in the server's C++ hash
// map (``src/parameter/kv_map.h`` / ``src/util/localizer.h`` [U] —
// SURVEY.md #11/#20).  Here the map is host-side (the device table is a
// dense HBM array indexed by the slots this map hands out), and at Criteo
// rates (16k batch x 39 slots) a Python-level loop — or even vectorized
// numpy probing, which pays a full batch-sized temporary per probe round —
// is the bottleneck (VERDICT r1 weak #3).  This is a flat open-addressing
// table (linear probing, power-of-two size, load factor <= 1/2) with the
// exact assign() semantics of utils.keys.Localizer:
//
//   PAD_KEY (2^64-1)        -> capacity  (the trash row)
//   known key               -> its stable slot
//   new key, vocab not full -> next sequential id (arrival order)
//   new key, vocab full     -> key % capacity  (feature-hash overflow,
//                              NOT cached; sets the overflow flag)
//
// ABI is plain C for ctypes.

#include <algorithm>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <new>
#include <vector>

namespace {

constexpr uint64_t kEmpty = 0xFFFFFFFFFFFFFFFFull;  // == PAD_KEY

inline uint64_t mix64(uint64_t x) {
  // splitmix64 avalanche — same constants as utils.keys.mix64(seed=0), so
  // probe distributions match the Python fallback (not semantically
  // required, but keeps perf characteristics identical).
  x *= 0xFF51AFD7ED558CCDull;
  x ^= x >> 33;
  x *= 0xC4CEB9FE1A85EC53ull;
  x ^= x >> 33;
  return x;
}

struct KeyMap {
  int64_t capacity = 0;   // max vocab (slot ids are 0..capacity-1)
  int64_t n = 0;          // assigned vocab size
  uint64_t size = 0;      // table size, power of two
  uint64_t mask = 0;
  uint64_t* keys = nullptr;
  int32_t* vals = nullptr;
  bool overflowed = false;
  bool grow_failed = false;  // OOM latch: stop re-attempting huge mallocs

  bool alloc(uint64_t new_size) {
    uint64_t* new_keys =
        static_cast<uint64_t*>(malloc(new_size * sizeof(uint64_t)));
    int32_t* new_vals =
        static_cast<int32_t*>(malloc(new_size * sizeof(int32_t)));
    if (!new_keys || !new_vals) {  // OOM must not leave dangling pointers
      free(new_keys);
      free(new_vals);
      return false;
    }
    size = new_size;
    mask = new_size - 1;
    keys = new_keys;
    vals = new_vals;
    memset(keys, 0xFF, new_size * sizeof(uint64_t));  // all kEmpty
    return true;
  }

  bool grow() {
    uint64_t old_size = size;
    uint64_t* old_keys = keys;
    int32_t* old_vals = vals;
    if (!alloc(size * 2)) {
      // OOM: keep the old table intact.  The map still works — inserts
      // continue until the table is literally full; assign_one falls back
      // to feature hashing at capacity, so correctness is preserved.  The
      // latch stops every later insert from re-attempting the same
      // multi-hundred-MB malloc pair under memory pressure.
      keys = old_keys;
      vals = old_vals;
      size = old_size;
      mask = old_size - 1;
      grow_failed = true;
      return false;
    }
    for (uint64_t i = 0; i < old_size; ++i) {
      if (old_keys[i] == kEmpty) continue;
      uint64_t p = mix64(old_keys[i]) & mask;
      while (keys[p] != kEmpty) p = (p + 1) & mask;
      keys[p] = old_keys[i];
      vals[p] = old_vals[i];
    }
    free(old_keys);
    free(old_vals);
    return true;
  }

  // find-or-insert one key; returns its slot
  inline int32_t assign_one(uint64_t k) {
    uint64_t p = mix64(k) & mask;
    // Bounded probe: after grow()-OOM the load factor may exceed 1/2, and a
    // literally full table would otherwise spin forever on an absent key.
    for (uint64_t probes = 0; probes < size; ++probes) {
      uint64_t cur = keys[p];
      if (cur == k) return vals[p];
      if (cur == kEmpty) {
        if (n < capacity) {
          int32_t slot = static_cast<int32_t>(n++);
          keys[p] = k;
          vals[p] = slot;
          if (static_cast<uint64_t>(n) * 2 > size && !grow_failed) grow();
          return slot;
        }
        break;
      }
      p = (p + 1) & mask;
    }
    overflowed = true;
    return static_cast<int32_t>(k % static_cast<uint64_t>(capacity));
  }
};

// ---------------------------------------------------------------------------
// One-pass batch localization for the STATELESS maps of utils/keys.py
// (HashLocalizer at 64 and 32 hash bits, IdentityLocalizer): what
// ``localize_to_slots`` returns -- the sorted distinct slots of a batch and
// every position's rank among them -- without sorting the positions.  A slot
// there is a pure function of its key, so "unique keys, assign, unique slots"
// and "assign every position, unique slots" are the same set and the same
// inverse: each position's slot is computed as the Python class computes it,
// deduplicated through an open-addressing table in first-seen order, and
// only the distinct slots (tens of thousands of a Zipf batch's hundreds of
// thousands of positions) are sorted.

constexpr uint32_t kNoSlot = 0xFFFFFFFFu;  // capacity < 2^31 - 1: never a slot

enum SlotKind : int { kHash64 = 0, kHash32 = 1, kIdentity = 2 };

inline uint32_t mix32(uint32_t x) {  // murmur3 fmix32 == utils.keys.mix32
  x ^= x >> 16;
  x *= 0x85EBCA6Bu;
  x ^= x >> 13;
  x *= 0xC2B2AE35u;
  x ^= x >> 16;
  return x;
}

// Scratch of one calling thread, kept between calls: workers localize
// concurrently (ctypes releases the GIL), and a steady-state call allocates
// nothing here.
struct Dedup {
  struct Entry {
    uint32_t slot;  // kNoSlot where empty
    int32_t id;     // the slot's first-seen id
  };
  std::vector<Entry> table;     // open addressing, load factor <= 1/2
  std::vector<uint64_t> order;  // slot << 32 | first-seen id: by id while
                                // the positions pass, then sorted by slot
                                // (what ps_localize_take hands out)
  std::vector<uint64_t> order_tmp;
  std::vector<int32_t> rank;    // first-seen id -> rank among the sorted
  uint32_t mask = 0;
  int shift = 0;  // 32 - log2(table.size())

  // Fibonacci hashing, the product's HIGH bits: identity slots are dense or
  // strided ids, and the low bits of a stride of 2^k are all alike.
  inline uint32_t home(uint32_t slot) const {
    return static_cast<uint32_t>((slot * 0x9E3779B1u) >> shift);
  }

  void resize_table(size_t size) {
    table.assign(size, Entry{kNoSlot, 0});
    mask = static_cast<uint32_t>(size - 1);
    shift = 32;
    while ((size_t{1} << (32 - shift)) < size) --shift;
  }

  // The table starts a call sized for the last call's distinct slots (a
  // thread's batches are alike) and grows from there: a table left at its
  // high-water mark would make every small batch pay for clearing it.
  void reset() {
    size_t size = 1 << 12;
    while (size < order.size() * 2) size *= 2;
    resize_table(size);
    order.clear();
  }

  void grow() {
    resize_table(table.size() * 2);
    for (size_t id = 0; id < order.size(); ++id) {
      const uint32_t slot = static_cast<uint32_t>(order[id] >> 32);
      uint32_t p = home(slot);
      while (table[p].slot != kNoSlot) p = (p + 1) & mask;
      table[p] = Entry{slot, static_cast<int32_t>(id)};
    }
  }

  // find-or-insert; returns the slot's first-seen id
  inline int32_t id_of(uint32_t slot) {
    uint32_t p = home(slot);
    for (;;) {
      const Entry e = table[p];
      if (e.slot == slot) return e.id;
      if (e.slot == kNoSlot) break;
      p = (p + 1) & mask;
    }
    const size_t id = order.size();
    table[p] = Entry{slot, static_cast<int32_t>(id)};
    order.push_back((static_cast<uint64_t>(slot) << 32) | id);
    if (order.size() * 2 > table.size()) grow();
    return static_cast<int32_t>(id);
  }

  // ``order`` by its high word, the slot (< 2^31): an LSD radix sort of
  // three 11-bit digits, linear where a comparison sort of the distinct
  // slots was a third of the whole call.
  void sort_order() {
    order_tmp.resize(order.size());
    for (int digit = 32; digit < 64; digit += 11) {
      size_t start[2048] = {0};
      for (uint64_t v : order) ++start[(v >> digit) & 2047];
      size_t at = 0;
      for (size_t& c : start) {
        const size_t count = c;
        c = at;
        at += count;
      }
      for (uint64_t v : order) order_tmp[start[(v >> digit) & 2047]++] = v;
      order.swap(order_tmp);
    }
  }
};

thread_local Dedup tls_dedup;

// Pass one: inverse[i] = first-seen id of position i's slot.  Returns false
// on an identity key outside [0, capacity) and leaves the smallest such key
// in *bad (what IdentityLocalizer.assign names: the first of the sorted keys).
template <int kKind>
bool first_seen_ids(Dedup& d, const uint64_t* in, int64_t n, uint64_t capacity,
                    uint64_t seed, int32_t* inverse, uint64_t* bad) {
  const uint32_t trash = static_cast<uint32_t>(capacity);
  const uint32_t seed32 = static_cast<uint32_t>(seed);
  bool ok = true;
  for (int64_t i = 0; i < n; ++i) {
    const uint64_t k = in[i];
    uint32_t slot;
    if (k == kEmpty) {
      slot = trash;
    } else if (kKind == kHash64) {
      slot = static_cast<uint32_t>(mix64(k ^ seed) % capacity);
    } else if (kKind == kHash32) {
      slot = mix32(static_cast<uint32_t>(k) ^ seed32) % trash;
    } else if (k < capacity) {
      slot = static_cast<uint32_t>(k);
    } else {
      if (ok || k < *bad) *bad = k;
      ok = false;
      continue;
    }
    inverse[i] = d.id_of(slot);
  }
  return ok;
}

// A server's leg against its shard map (ps_localize_shard), keys of type K
// widened to signed 64 bits.  ``segs`` holds a flag a segment on entry to
// the loop and the touched segments' indices, ascending, on return.
template <typename K>
int64_t localize_shard(const K* keys, int64_t n, int64_t grows,
                       const int64_t* starts, const int64_t* ends,
                       const int64_t* locals, int64_t nseg, int32_t trash,
                       int32_t* out, int64_t* segs, int64_t* counts) {
  std::fill(segs, segs + nseg, 0);
  int64_t n_real = 0, upto = 0;
  int64_t seg = 0;  // the previous real key's segment: where a search starts
  for (int64_t i = 0; i < n; ++i) {
    const int64_t g = static_cast<int64_t>(keys[i]);
    if (g >= grows) {
      out[i] = trash;
      continue;
    }
    // a row offset is >= 0, so a negative key ends here too
    if (nseg == 0 || g < starts[0]) return -1;
    // the last segment that starts at or before g (searchsorted "right" - 1)
    if (g < starts[seg] || (seg + 1 < nseg && g >= starts[seg + 1])) {
      seg = (std::upper_bound(starts, starts + nseg, g) - starts) - 1;
    }
    if (g >= ends[seg]) return -1;
    out[i] = static_cast<int32_t>(g - starts[seg] + locals[seg]);
    segs[seg] = 1;
    ++n_real;
    upto = i + 1;
  }
  int64_t m = 0;
  for (int64_t s = 0; s < nseg; ++s) {
    if (segs[s]) segs[m++] = s;
  }
  counts[0] = n_real;
  counts[1] = upto;
  return m;
}

}  // namespace

extern "C" {

void* ps_keymap_new(int64_t capacity) {
  if (capacity <= 0) return nullptr;
  auto* m = new KeyMap();
  m->capacity = capacity;
  if (!m->alloc(1 << 16)) {  // OOM -> nullptr; Python raises MemoryError
    delete m;
    return nullptr;
  }
  return m;
}

void ps_keymap_free(void* h) {
  auto* m = static_cast<KeyMap*>(h);
  if (!m) return;
  free(m->keys);
  free(m->vals);
  delete m;
}

int64_t ps_keymap_len(void* h) { return static_cast<KeyMap*>(h)->n; }

int ps_keymap_overflowed(void* h) {
  return static_cast<KeyMap*>(h)->overflowed ? 1 : 0;
}

// Assign slots for n keys (PAD -> capacity). Sequential; insertion order is
// the arrival order, matching the Python Localizer exactly.
void ps_keymap_assign(void* h, const uint64_t* in, int64_t n, int32_t* out) {
  auto* m = static_cast<KeyMap*>(h);
  const int32_t trash = static_cast<int32_t>(m->capacity);
  for (int64_t i = 0; i < n; ++i) {
    uint64_t k = in[i];
    out[i] = (k == kEmpty) ? trash : m->assign_one(k);
  }
}

// Localize n keys through a stateless map (``kind``: 0 mix64 % capacity,
// 1 mix32 of the truncated key % capacity, 2 the key itself; PAD ->
// capacity).  Writes each position's rank among the batch's sorted distinct
// slots to ``inverse`` and returns their count; the slots themselves stay in
// the calling thread's scratch for ps_localize_take.  -1: an identity key is
// out of range (*bad names it, nothing is clamped); -2: out of memory.
int64_t ps_localize_slots(int kind, const uint64_t* in, int64_t n,
                          int64_t capacity, uint64_t seed, int32_t* inverse,
                          uint64_t* bad) {
  Dedup& d = tls_dedup;
  const uint64_t cap = static_cast<uint64_t>(capacity);
  try {
    d.reset();
    bool ok;
    if (kind == kHash64) {
      ok = first_seen_ids<kHash64>(d, in, n, cap, seed, inverse, bad);
    } else if (kind == kHash32) {
      ok = first_seen_ids<kHash32>(d, in, n, cap, seed, inverse, bad);
    } else {
      ok = first_seen_ids<kIdentity>(d, in, n, cap, seed, inverse, bad);
    }
    if (!ok) return -1;
    d.sort_order();
    const size_t m = d.order.size();
    d.rank.resize(m);
    for (size_t r = 0; r < m; ++r) {
      d.rank[d.order[r] & 0xFFFFFFFFu] = static_cast<int32_t>(r);
    }
    for (int64_t i = 0; i < n; ++i) inverse[i] = d.rank[inverse[i]];
    return static_cast<int64_t>(m);
  } catch (const std::bad_alloc&) {
    return -2;
  }
}

// The calling thread's last ps_localize_slots result into ``out[bucket]``:
// the sorted distinct slots, then ``pad`` (the trash row) up to the bucket.
void ps_localize_take(int32_t* out, int64_t bucket, int32_t pad) {
  const std::vector<uint64_t>& order = tls_dedup.order;
  const int64_t m = std::min<int64_t>(order.size(), bucket);
  for (int64_t r = 0; r < m; ++r) out[r] = static_cast<int32_t>(order[r] >> 32);
  std::fill(out + m, out + bucket, pad);
}

// A server's localization of one request's leg, in one pass: ``n`` global
// keys of ``key_bytes`` (4: int32, 8: int64) each, compared as signed 64-bit
// values, against the shard map ``starts/ends/locals[nseg]`` (the owned
// segments, ascending).  A key >= ``grows`` (a pad) -> ``trash``; a key g in
// owned segment i -> g - starts[i] + locals[i].  The keys need not be
// sorted; sorted keys find their segment where the last one was.  Returns
// the count of touched segments and leaves their indices, ascending, in
// ``segs[nseg]``; counts[0] = the real keys, counts[1] = one past the last
// real key's position.  -1: a real key is in no owned segment (or is
// negative): the request fences, and ``out`` / ``segs`` hold nothing.
int64_t ps_localize_shard(const void* keys, int key_bytes, int64_t n,
                          int64_t grows, const int64_t* starts,
                          const int64_t* ends, const int64_t* locals,
                          int64_t nseg, int32_t trash, int32_t* out,
                          int64_t* segs, int64_t* counts) {
  if (key_bytes == 4) {
    return localize_shard(static_cast<const int32_t*>(keys), n, grows, starts,
                          ends, locals, nseg, trash, out, segs, counts);
  }
  return localize_shard(static_cast<const int64_t*>(keys), n, grows, starts,
                        ends, locals, nseg, trash, out, segs, counts);
}

}  // extern "C"
