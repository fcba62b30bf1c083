// Sanitizer smoke test: links against the ASan/TSan-built libkbstore.so
// and drives the native engine path end to end — batches (put / CAS /
// delete), snapshot gets, iterators both directions, bulk scan pages,
// partition sampling, version pruning, the WAL persistence cycle
// (open_at -> reopen -> checkpoint -> reopen), and the dump/apply
// replication round-trip. Every code path it touches runs under
// -fsanitize, so an OOB read, leak, UB shift, or (under TSan) a data race
// in kbstore.cc fails the build's `make -C native asan-check`.
//
// Prints "SMOKE OK" and exits 0 on success; any sanitizer report aborts
// with a nonzero exit (halt_on_error is set by the make target).

#include <sys/stat.h>

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>

extern "C" {
void* kb_open();
void* kb_open_at(const char* dir, int fsync_commits);
int kb_checkpoint(void* s);
void kb_close(void* s);
uint64_t kb_tso(void* s);
int kb_get(void* s, const uint8_t* key, size_t klen, uint64_t snap,
           uint8_t** out, size_t* out_len);
void kb_free(void* p);
void* kb_batch_begin(void* s);
void kb_batch_put(void* b, const uint8_t* k, size_t kl, const uint8_t* v,
                  size_t vl, int64_t ttl);
void kb_batch_put_if_absent(void* b, const uint8_t* k, size_t kl,
                            const uint8_t* v, size_t vl, int64_t ttl);
void kb_batch_cas(void* b, const uint8_t* k, size_t kl, const uint8_t* nv,
                  size_t nvl, const uint8_t* ov, size_t ovl, int64_t ttl);
void kb_batch_del(void* b, const uint8_t* k, size_t kl);
int kb_batch_commit(void* b, int64_t* conflict_idx, uint8_t** conflict_val,
                    size_t* conflict_len, int* conflict_has_val);
void* kb_iter_open(void* s, const uint8_t* start, size_t slen,
                   const uint8_t* end, size_t elen, uint64_t snap,
                   uint64_t limit, int reverse);
int kb_iter_next(void* itp, const uint8_t** key, size_t* klen,
                 const uint8_t** val, size_t* vlen);
void kb_iter_close(void* itp);
uint64_t kb_scan_page(void* s, const uint8_t* start, size_t slen,
                      const uint8_t* end, size_t elen, uint64_t snap,
                      uint64_t max_rows, uint8_t* key_arena, uint64_t key_cap,
                      uint64_t* key_offs, uint8_t* val_arena, uint64_t val_cap,
                      uint64_t* val_offs, int* more);
int kb_split_keys(void* s, int n_parts, uint8_t* borders, size_t row_width,
                  size_t* border_lens);
uint64_t kb_key_count(void* s);
uint64_t kb_version_count(void* s);
uint64_t kb_prune(void* s, uint64_t keep_after_ts);
int kb_dump_wire(void* s, uint8_t** out, size_t* out_len, uint64_t* ts_out);
int kb_apply_record(void* s, const uint8_t* rec, size_t len, int reset,
                    uint64_t* applied_ts);
size_t kb_wire_gather(const uint64_t* runs, size_t n_runs, uint8_t* out,
                      size_t out_cap);
size_t kb_wire_read(const uint64_t* parts, size_t n_parts, size_t key_chunks,
                    const uint64_t* dict, size_t n_ov, const uint8_t* ov_keys,
                    const uint64_t* ov_key_offs, const uint8_t* ov_vals,
                    const uint64_t* ov_val_offs, const uint64_t* ov_revs,
                    const uint8_t* ov_dead, uint64_t limit, uint8_t* out,
                    size_t out_cap, uint64_t* rows, int* more);
}

#define CHECK(cond)                                                     \
  do {                                                                  \
    if (!(cond)) {                                                      \
      fprintf(stderr, "SMOKE FAIL %s:%d: %s\n", __FILE__, __LINE__, #cond); \
      exit(1);                                                          \
    }                                                                   \
  } while (0)

static const uint8_t* B(const char* s) {
  return reinterpret_cast<const uint8_t*>(s);
}

static void put1(void* s, const char* k, const char* v) {
  void* b = kb_batch_begin(s);
  kb_batch_put(b, B(k), strlen(k), B(v), strlen(v), 0);
  int64_t ci = -1;
  uint8_t* cv = nullptr;
  size_t cl = 0;
  int has = 0;
  CHECK(kb_batch_commit(b, &ci, &cv, &cl, &has) == 0);
}

static std::string get1(void* s, const char* k, uint64_t snap) {
  uint8_t* out = nullptr;
  size_t out_len = 0;
  if (kb_get(s, B(k), strlen(k), snap, &out, &out_len) != 0) return "<miss>";
  std::string v(reinterpret_cast<char*>(out), out_len);
  kb_free(out);
  return v;
}

static void smoke_memory_engine() {
  void* s = kb_open();
  CHECK(kb_tso(s) == 0);

  // batch semantics: plain put, guarded put, CAS success + conflict
  for (int i = 0; i < 64; ++i) {
    char k[32], v[32];
    snprintf(k, sizeof k, "key/%03d", i);
    snprintf(v, sizeof v, "val-%03d", i);
    put1(s, k, v);
  }
  uint64_t snap_before = kb_tso(s);
  put1(s, "key/000", "val-000b");
  CHECK(get1(s, "key/000", 0) == "val-000b");
  CHECK(get1(s, "key/000", snap_before) == "val-000");  // snapshot isolation

  void* b = kb_batch_begin(s);
  kb_batch_put_if_absent(b, B("key/000"), 7, B("x"), 1, 0);  // occupied
  int64_t ci = -1;
  uint8_t* cv = nullptr;
  size_t cl = 0;
  int has = 0;
  CHECK(kb_batch_commit(b, &ci, &cv, &cl, &has) == 1);
  CHECK(ci == 0);
  if (has) {
    CHECK(cl == 8 && memcmp(cv, "val-000b", 8) == 0);
    kb_free(cv);
  }

  b = kb_batch_begin(s);
  kb_batch_cas(b, B("key/001"), 7, B("val-001-new"), 11, B("val-001"), 7, 0);
  kb_batch_del(b, B("key/002"), 7);
  CHECK(kb_batch_commit(b, &ci, &cv, &cl, &has) == 0);
  CHECK(get1(s, "key/001", 0) == "val-001-new");
  CHECK(get1(s, "key/002", 0) == "<miss>");

  // iterators: forward windowed, reverse, limit
  void* it = kb_iter_open(s, B("key/010"), 7, B("key/020"), 7, 0, 0, 0);
  int rows = 0;
  const uint8_t *kp, *vp;
  size_t kl, vl;
  while (kb_iter_next(it, &kp, &kl, &vp, &vl) == 0) ++rows;
  kb_iter_close(it);
  CHECK(rows == 10);
  it = kb_iter_open(s, B("key/020"), 7, B("key/010"), 7, 0, 3, 1);
  rows = 0;
  while (kb_iter_next(it, &kp, &kl, &vp, &vl) == 0) ++rows;
  kb_iter_close(it);
  CHECK(rows == 3);

  // bulk scan page (the etcd list hot path)
  uint8_t karena[4096], varena[4096];
  uint64_t koffs[128], voffs[128];
  int more = 0;
  uint64_t n = kb_scan_page(s, B(""), 0, B(""), 0, 0, 100, karena,
                            sizeof karena, koffs, varena, sizeof varena,
                            voffs, &more);
  CHECK(n == 63);  // 64 puts + 1 delete, key/000 rewritten in place
  CHECK(koffs[n] <= sizeof karena && voffs[n] <= sizeof varena);

  // partition sampling + counters + prune
  uint8_t borders[8 * 64];
  size_t blens[8];
  int got = kb_split_keys(s, 4, borders, 64, blens);
  CHECK(got >= 1 && got <= 3);
  CHECK(kb_key_count(s) == 64);  // 63 live + the tombstoned key/002
  CHECK(kb_version_count(s) >= 64);
  uint64_t freed = kb_prune(s, kb_tso(s));
  CHECK(freed >= 1);                // superseded versions + the dead key
  CHECK(kb_key_count(s) == 63);     // tombstone chain physically erased
  CHECK(kb_version_count(s) == 63);

  // replication round-trip: dump the store, apply into a fresh one
  uint8_t* dump = nullptr;
  size_t dlen = 0;
  uint64_t dts = 0;
  CHECK(kb_dump_wire(s, &dump, &dlen, &dts) == 0);
  void* s2 = kb_open();
  uint64_t ats = 0;
  CHECK(kb_apply_record(s2, dump, dlen, 1, &ats) == 0);
  kb_free(dump);
  CHECK(ats == dts);
  CHECK(get1(s2, "key/001", 0) == "val-001-new");
  CHECK(kb_key_count(s2) == 63);
  kb_close(s2);
  kb_close(s);
}

static void smoke_wal_cycle(const char* dir) {
  mkdir(dir, 0755);  // fresh run dir; EEXIST on reruns is fine
  void* s = kb_open_at(dir, 0);
  CHECK(s != nullptr);
  put1(s, "wal/a", "1");
  put1(s, "wal/b", "2");
  kb_close(s);

  s = kb_open_at(dir, 0);  // WAL replay
  CHECK(s != nullptr);
  CHECK(get1(s, "wal/a", 0) == "1");
  put1(s, "wal/c", "3");
  CHECK(kb_checkpoint(s) == 0);  // snapshot + WAL truncate
  put1(s, "wal/d", "4");
  kb_close(s);

  s = kb_open_at(dir, 0);  // snapshot + tail replay
  CHECK(s != nullptr);
  CHECK(get1(s, "wal/b", 0) == "2");
  CHECK(get1(s, "wal/c", 0) == "3");
  CHECK(get1(s, "wal/d", 0) == "4");
  kb_close(s);
}

// The mirror's wire gather: three rows of a five-row arena, picked out of
// order of the arena (rows index the value offsets, not the key matrix), an
// empty value, a revision that needs a multi-byte varint, as TWO runs of
// one source (rows 0-1, then row 2: what a spliced overlay entry makes of a
// reply) — into heap buffers of EXACTLY the size it asks for, and one byte
// short of it, so ASan sees any write past the end and any read past the
// inputs.
static void smoke_wire_gather() {
  const size_t stride = 8;
  uint8_t* keys = static_cast<uint8_t*>(calloc(3, stride));
  memcpy(keys, "/a", 2);
  memcpy(keys + stride, "/bb", 3);
  memcpy(keys + 2 * stride, "/ccccccc", 8);  // a key as wide as the matrix
  int32_t* lens = static_cast<int32_t*>(malloc(3 * sizeof(int32_t)));
  lens[0] = 2, lens[1] = 3, lens[2] = 8;
  uint64_t* revs = static_cast<uint64_t*>(malloc(3 * sizeof(uint64_t)));
  revs[0] = 7, revs[1] = 300, revs[2] = (1ULL << 40) + 5;
  const char* vals = "v0v1-longerv3";  // rows 0..4: "v0" "v1-longer" "" "v3" ""
  uint8_t* arena = static_cast<uint8_t*>(malloc(13));
  memcpy(arena, vals, 13);
  uint64_t* offs = static_cast<uint64_t*>(malloc(6 * sizeof(uint64_t)));
  offs[0] = 0, offs[1] = 2, offs[2] = 11, offs[3] = 11, offs[4] = 13,
  offs[5] = 13;
  int64_t* rows = static_cast<int64_t*>(malloc(3 * sizeof(int64_t)));
  rows[0] = 1, rows[1] = 2, rows[2] = 4;
  auto word = [](const void* p) { return reinterpret_cast<uint64_t>(p); };
  uint64_t* runs = static_cast<uint64_t*>(malloc(16 * sizeof(uint64_t)));
  const uint64_t table[16] = {
      word(keys), stride, word(lens), word(revs),
      word(arena), word(offs), word(rows), 2,
      word(keys + 2 * stride), stride, word(lens + 2), word(revs + 2),
      word(arena), word(offs), word(rows + 2), 1};
  memcpy(runs, table, sizeof(table));

  size_t need = kb_wire_gather(runs, 2, nullptr, 0);
  // row: 0x12 len | 0x0A kl key | 0x10 rev | 0x18 rev | 0x20 1 | 0x2A vl val
  size_t want = (2 + 2 + 2 + 2 * 2 + 2 + 2 + 9) + (2 + 2 + 3 + 2 * 3 + 2 + 2) +
                (2 + 2 + 8 + 2 * 7 + 2 + 2);
  if (need != want) {
    fprintf(stderr, "wire gather size %zu != %zu\n", need, want);
    abort();
  }
  uint8_t* small = static_cast<uint8_t*>(malloc(need - 1));
  if (kb_wire_gather(runs, 2, small, need - 1) != need)
    abort();  // too short: the size again, nothing written
  free(small);
  uint8_t* out = static_cast<uint8_t*>(malloc(need));
  if (kb_wire_gather(runs, 2, out, need) != need) abort();
  const uint8_t first[] = {0x12, 21,   0x0A, 2,   '/', 'a', 0x10, 7, 0x18,
                           7,    0x20, 1,    0x2A, 9,  'v', '1',  '-'};
  if (memcmp(out, first, sizeof(first)) != 0 || out[need - 2] != 0x2A ||
      out[need - 1] != 0)
    abort();
  if (kb_wire_gather(runs, 0, out, need) != 0) abort();
  free(out), free(runs), free(rows), free(offs), free(arena), free(revs),
      free(lens), free(keys);
}

// The wire read, whole (key decode, overlay merge, cut): one encoded
// partition of four rows in key order under a two-bucket dictionary, three
// of them visible, every array on the heap at EXACTLY its size so ASan sees
// any read past an input and any write past the reply — with an empty
// overlay, a buffer one byte short, an overlay that inserts, supersedes and
// deletes, a cut at `limit`, an overlay-only reply, a raw mirror, and a row
// index and a key code the arrays do not hold.
static void smoke_wire_read() {
  auto heap = [](const void* src, size_t n) {
    void* p = malloc(n ? n : 1);
    memcpy(p, src, n);
    return p;
  };
  auto word = [](const void* p) { return reinterpret_cast<uint64_t>(p); };
  // rows "/x/aa", "/yy/", "/yy/b" (not visible), "/yy/cd": code 0 strips
  // "/x/", code 1 "/yy/"; suffix_width 4, so 2 chunks a row
  const uint32_t keys_v[8] = {0, 0x61610000u, 1, 0,
                              1, 0x62000000u, 1, 0x63640000u};
  const int32_t lens_v[4] = {2, 0, 1, 2};
  const uint64_t revs_v[4] = {5, 6, 7, 300};
  const uint64_t offs_v[5] = {0, 2, 2, 5, 9};  // "v0" "" "v-2" "v--3"
  const int32_t idx_v[3] = {0, 1, 3};
  const int64_t strip_lens_v[2] = {3, 4};
  const uint8_t strips_v[8] = {'/', 'x', '/', 0, '/', 'y', 'y', '/'};
  auto* keys = static_cast<uint32_t*>(heap(keys_v, sizeof keys_v));
  auto* lens = static_cast<int32_t*>(heap(lens_v, sizeof lens_v));
  auto* revs = static_cast<uint64_t*>(heap(revs_v, sizeof revs_v));
  auto* arena = static_cast<uint8_t*>(heap("v0v-2v--3", 9));
  auto* offs = static_cast<uint64_t*>(heap(offs_v, sizeof offs_v));
  auto* idx = static_cast<int32_t*>(heap(idx_v, sizeof idx_v));
  auto* strip_lens =
      static_cast<int64_t*>(heap(strip_lens_v, sizeof strip_lens_v));
  auto* strips = static_cast<uint8_t*>(heap(strips_v, sizeof strips_v));
  const uint64_t part_v[8] = {word(keys), word(lens), word(revs), word(arena),
                              word(offs), 4,          word(idx),  3};
  auto* part = static_cast<uint64_t*>(heap(part_v, sizeof part_v));
  const uint64_t dict_v[6] = {word(strip_lens), word(strips), 4, 2, 4, 16};
  auto* dict = static_cast<uint64_t*>(heap(dict_v, sizeof dict_v));
  // the overlay, in key order: an insert before every row, a new value for
  // "/yy/" (the mirror's row drops out), "/yy/cd" deleted, an insert last
  const uint64_t okoffs_v[5] = {0, 2, 6, 12, 14};
  const uint64_t ovoffs_v[5] = {0, 1, 4, 4, 4};  // "A" "new" (dead) ""
  const uint64_t orevs_v[4] = {900, 901, 0, 903};
  const uint8_t odead_v[4] = {0, 0, 1, 0};
  auto* ok = static_cast<uint8_t*>(heap("/a/yy//yy/cd/z", 14));
  auto* oko = static_cast<uint64_t*>(heap(okoffs_v, sizeof okoffs_v));
  auto* ovl = static_cast<uint8_t*>(heap("Anew", 4));
  auto* ovo = static_cast<uint64_t*>(heap(ovoffs_v, sizeof ovoffs_v));
  auto* orv = static_cast<uint64_t*>(heap(orevs_v, sizeof orevs_v));
  auto* odd = static_cast<uint8_t*>(heap(odead_v, sizeof odead_v));
  uint64_t rows = 0;
  int more = -1;
  uint8_t* out = nullptr;
  // one read into a heap buffer of exactly `cap` bytes (none for 0)
  auto read = [&](const uint64_t* parts, size_t n_parts, const uint64_t* d,
                  size_t n_ov, uint64_t limit, size_t cap) {
    free(out);
    out = cap ? static_cast<uint8_t*>(malloc(cap)) : nullptr;
    return kb_wire_read(parts, n_parts, 2, d, n_ov, ok, oko, ovl, ovo, orv,
                        odd, limit, out, cap, &rows, &more);
  };

  // an empty overlay, the size first: three rows, each key decoded
  // row: 0x12 len | 0x0A kl key | 0x10 rev | 0x18 rev | 0x20 1 | 0x2A vl val
  size_t want = (2 + 2 + 5 + 4 + 2 + 2 + 2) + (2 + 2 + 4 + 4 + 2 + 2) +
                (2 + 2 + 6 + 6 + 2 + 2 + 4);
  size_t need = read(part, 1, dict, 0, 0, 0);
  if (need != want) fprintf(stderr, "wire read size %zu != %zu\n", need, want);
  CHECK(need == want && rows == 3 && more == 0);
  // a buffer too short: the size again, nothing written
  CHECK(read(part, 1, dict, 0, 0, need - 1) == need);
  CHECK(read(part, 1, dict, 0, 0, need) == need);
  const uint8_t first[] = {0x12, 17,   0x0A, 5,    '/', 'x', '/', 'a', 'a', 0x10,
                           5,    0x18, 5,    0x20, 1,   0x2A, 2,  'v', '0'};
  CHECK(memcmp(out, first, sizeof first) == 0);
  CHECK(out[need - 4] == 'v' && out[need - 15] == 'd' && out[need - 1] == '3');

  // the overlay merged: "/a", "/x/aa", "/yy/" as the overlay has it, "/z"
  need = read(part, 1, dict, 4, 0, 0);
  CHECK(need != SIZE_MAX && rows == 4 && more == 0);
  CHECK(read(part, 1, dict, 4, 0, need) == need && rows == 4);
  const uint8_t head[] = {0x12, 15, 0x0A, 2, '/', 'a', 0x10, 0x84, 0x07};
  CHECK(memcmp(out, head, sizeof head) == 0);
  CHECK(out[need - 1] == 0 && out[need - 11] == 'z');

  // a cut at `limit`: two rows out and more set; a limit on the last row
  need = read(part, 1, dict, 4, 2, 0);
  CHECK(rows == 2 && more == 1);
  CHECK(read(part, 1, dict, 4, 2, need) == need && rows == 2 && more == 1);
  read(part, 1, dict, 4, 4, 0);
  CHECK(rows == 4 && more == 0);
  read(part, 1, dict, 4, 3, 0);
  CHECK(rows == 3 && more == 1);

  // an overlay-only reply: no partition at all, the three live entries
  need = read(nullptr, 0, dict, 4, 0, 0);
  CHECK(rows == 3 && more == 0);
  CHECK(read(nullptr, 0, nullptr, 4, 0, need) == need && rows == 3);

  // a raw mirror (no dictionary): the chunks are the key, cut at its length
  need = read(part, 1, nullptr, 0, 0, 0);
  CHECK(read(part, 1, nullptr, 0, 0, need) == need && rows == 3);
  CHECK(out[3] == 2 && out[4] == 0 && out[5] == 0 && out[6] == 0x10);

  // a row index past the arrays, a code past the dictionary: refused
  idx[2] = 4;
  CHECK(read(part, 1, dict, 0, 0, 0) == SIZE_MAX);
  idx[2] = 3;
  keys[6] = 2;
  CHECK(read(part, 1, dict, 4, 0, 0) == SIZE_MAX);
  free(out), free(odd), free(orv), free(ovo), free(ovl), free(oko), free(ok),
      free(dict), free(part), free(strips), free(strip_lens), free(idx),
      free(offs), free(arena), free(revs), free(lens), free(keys);
}

int main(int argc, char** argv) {
  smoke_memory_engine();
  smoke_wire_gather();
  smoke_wire_read();
  if (argc > 1) smoke_wal_cycle(argv[1]);
  printf("SMOKE OK\n");
  return 0;
}
