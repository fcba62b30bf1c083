// kbstore — embedded versioned KV engine (C ABI for ctypes).
//
// The native host block manager of the framework (SURVEY §2.8): plays the
// role Badger plays for the reference (embedded single-node engine,
// pkg/storage/badger) and serves as the authoritative host store under the
// TPU mirror engine (storage/tpu). Not a port of anything: an ordered map of
// version chains with snapshot isolation, conditional write batches that
// report CAS conflicts with the observed value, a logical commit clock
// (timestamp oracle), native TTL, chunked snapshot iterators, and key-space
// split sampling for partition-parallel scans.
//
// Engine contract (docs/storage_engine.md:3-15 of the reference): snapshot
// reads, bidirectional traversal, CAS write transactions, exposed logical
// clock; snapshot isolation + linearizable writes (one writer lock, readers
// concurrent via shared_mutex).

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <ctime>
#ifdef __unix__
#include <unistd.h>
#endif
#include <map>
#include <memory>
#include <mutex>
#include <shared_mutex>
#include <string>
#include <vector>

// Replication hook: invoked after every durable commit with the exact WAL
// record bytes (kbstored ships them to followers — the WAL *is* the
// replication stream, the role raft logs play for TiKV regions,
// tikv.go:123-153).
extern "C" typedef void (*kb_commit_cb)(void* ctx, const uint8_t* rec,
                                        size_t len, uint64_t ts);

namespace {

struct Version {
  uint64_t ts;
  bool deleted;
  double expire_at;  // 0 = no TTL
  std::string value;
};

struct Store {
  std::map<std::string, std::vector<Version>> data;
  uint64_t ts = 0;
  mutable std::shared_mutex mu;
  // durability (optional): write-ahead log appended per commit; snapshot
  // rewrites latest-only state and truncates the log (kb_checkpoint).
  std::string dir;     // empty = in-memory only
  FILE* wal = nullptr;
  bool fsync_commits = false;
  kb_commit_cb hook = nullptr;  // replication sink (see kb_set_commit_hook)
  void* hook_ctx = nullptr;

  ~Store() {
    if (wal != nullptr) fclose(wal);
  }

  const std::string* live(const std::string& key, uint64_t snap, double now) const {
    auto it = data.find(key);
    if (it == data.end()) return nullptr;
    const auto& versions = it->second;
    for (auto v = versions.rbegin(); v != versions.rend(); ++v) {
      if (v->ts <= snap) {
        if (v->deleted) return nullptr;
        if (v->expire_at != 0 && now >= v->expire_at) return nullptr;
        return &v->value;
      }
    }
    return nullptr;
  }
};

enum OpKind : int {
  OP_PUT = 0,
  OP_PUT_IF_ABSENT = 1,
  OP_CAS = 2,
  OP_DEL = 3,
  OP_DEL_CURRENT = 4,
};

struct Op {
  int kind;
  std::string key;
  std::string value;     // new value for puts
  std::string expected;  // old value for CAS / DelCurrent
  int64_t ttl_seconds;
};

struct Batch {
  Store* store;
  std::vector<Op> ops;
};

struct Iter {
  std::vector<std::pair<std::string, std::string>> buf;
  size_t pos = 0;
};

double wallclock() { return static_cast<double>(time(nullptr)); }

// --------------------------------------------------------------- durability
// Log record: [u32 KBW1][u64 ts][u32 nops] then per op:
// [u8 kind(0=put,1=del)][u32 klen][u32 vlen][f64 expire_at][key][val].
// Replay stops at the first torn/malformed record (crash-safe tail).
constexpr uint32_t kWalMagic = 0x4b425731;

struct AppliedOp {
  uint8_t kind;  // 0 put, 1 del
  std::string key;
  std::string value;
  double expire_at;
};

void serialize_record(std::string& out, uint64_t ts,
                      const std::vector<AppliedOp>& ops) {
  uint32_t magic = kWalMagic;
  uint32_t nops = static_cast<uint32_t>(ops.size());
  out.append(reinterpret_cast<const char*>(&magic), 4);
  out.append(reinterpret_cast<const char*>(&ts), 8);
  out.append(reinterpret_cast<const char*>(&nops), 4);
  for (const auto& op : ops) {
    uint32_t klen = op.key.size(), vlen = op.value.size();
    out.append(reinterpret_cast<const char*>(&op.kind), 1);
    out.append(reinterpret_cast<const char*>(&klen), 4);
    out.append(reinterpret_cast<const char*>(&vlen), 4);
    out.append(reinterpret_cast<const char*>(&op.expire_at), 8);
    out.append(op.key);
    out.append(op.value);
  }
}

bool write_record(FILE* f, uint64_t ts, const std::vector<AppliedOp>& ops) {
  std::string rec;
  serialize_record(rec, ts, ops);
  return fwrite(rec.data(), 1, rec.size(), f) == rec.size();
}

// Append pre-serialized record bytes to the WAL with the
// rollback-on-failure contract every commit site shares: a failed append
// truncates back to the record start so an acknowledged write is always
// replayable. Returns false on failure (caller must fail the commit).
bool append_wal_raw(Store* st, const std::string& rec) {
  if (st->wal == nullptr) return true;
  long rec_start = ftell(st->wal);
  bool logged = fwrite(rec.data(), 1, rec.size(), st->wal) == rec.size();
  if (logged) logged = fflush(st->wal) == 0;
  if (logged && st->fsync_commits) {
#ifdef __unix__
    logged = fsync(fileno(st->wal)) == 0;
#endif
  }
  if (!logged) {
    fflush(st->wal);
#ifdef __unix__
    if (rec_start >= 0 && ftruncate(fileno(st->wal), rec_start) == 0) {
      fseek(st->wal, rec_start, SEEK_SET);
    }
#endif
  }
  return logged;
}

// Serialize once, WAL-append; rec_out survives for the replication hook
// (fire AFTER the memory mutation so followers never see a commit the
// primary itself could still roll back).
bool log_commit(Store* st, uint64_t ts, const std::vector<AppliedOp>& ops,
                std::string* rec_out) {
  serialize_record(*rec_out, ts, ops);
  return append_wal_raw(st, *rec_out);
}

void fire_hook(Store* st, const std::string& rec, uint64_t ts) {
  if (st->hook != nullptr) {
    st->hook(st->hook_ctx, reinterpret_cast<const uint8_t*>(rec.data()),
             rec.size(), ts);
  }
}

// Replay records with ts > min_ts (records at or below min_ts are already
// covered by the snapshot — replaying them would push stale versions AFTER
// newer ones in the per-key vectors and corrupt live()).
void replay_file(Store* st, const std::string& path, uint64_t min_ts = 0) {
  FILE* f = fopen(path.c_str(), "rb");
  if (f == nullptr) return;
  while (true) {
    uint32_t magic = 0, nops = 0;
    uint64_t ts = 0;
    if (fread(&magic, 4, 1, f) != 1 || magic != kWalMagic) break;
    if (fread(&ts, 8, 1, f) != 1) break;
    if (fread(&nops, 4, 1, f) != 1) break;
    std::vector<AppliedOp> ops;
    ops.reserve(nops);
    bool ok = true;
    for (uint32_t i = 0; i < nops && ok; ++i) {
      AppliedOp op;
      uint32_t klen = 0, vlen = 0;
      ok = fread(&op.kind, 1, 1, f) == 1 && fread(&klen, 4, 1, f) == 1 &&
           fread(&vlen, 4, 1, f) == 1 && fread(&op.expire_at, 8, 1, f) == 1;
      if (ok && klen) {
        op.key.resize(klen);
        ok = fread(&op.key[0], 1, klen, f) == klen;
      }
      if (ok && vlen) {
        op.value.resize(vlen);
        ok = fread(&op.value[0], 1, vlen, f) == vlen;
      }
      if (ok) ops.push_back(std::move(op));
    }
    if (!ok) break;  // torn tail: discard the partial record
    if (ts > min_ts) {
      for (const auto& op : ops) {
        Version v;
        v.ts = ts;
        v.deleted = op.kind == 1;
        v.expire_at = op.expire_at;
        v.value = op.value;
        st->data[op.key].push_back(std::move(v));
      }
    }
    if (ts > st->ts) st->ts = ts;
  }
  fclose(f);
}

void fsync_dir(const std::string& dir) {
#ifdef __unix__
  FILE* d = fopen(dir.c_str(), "rb");
  if (d != nullptr) {
    fsync(fileno(d));
    fclose(d);
  }
#else
  (void)dir;
#endif
}

int checkpoint_locked(Store* st) {
  // latest-only snapshot at the current clock; history before it only
  // matters to in-flight snapshots, which do not survive a restart anyway
  std::string snap_tmp = st->dir + "/snapshot.kb.tmp";
  std::string snap = st->dir + "/snapshot.kb";
  std::string wal_path = st->dir + "/wal.kb";
  FILE* f = fopen(snap_tmp.c_str(), "wb");
  if (f == nullptr) return 1;
  double now = wallclock();
  std::vector<AppliedOp> ops;
  ops.reserve(st->data.size());
  for (const auto& entry : st->data) {
    const std::string* v = st->live(entry.first, st->ts, now);
    if (v == nullptr) continue;
    AppliedOp op;
    op.kind = 0;
    op.key = entry.first;
    op.value = *v;
    op.expire_at = entry.second.back().expire_at;
    ops.push_back(std::move(op));
  }
  bool ok = write_record(f, st->ts, ops);
  fflush(f);
#ifdef __unix__
  if (ok) ok = fsync(fileno(f)) == 0;  // snapshot bytes durable before rename
#endif
  fclose(f);
  if (!ok) return 1;
  if (rename(snap_tmp.c_str(), snap.c_str()) != 0) return 1;
  fsync_dir(st->dir);  // rename durable before the WAL is truncated
  if (st->wal != nullptr) fclose(st->wal);
  st->wal = fopen(wal_path.c_str(), "wb");  // truncate: snapshot covers it
  if (st->wal == nullptr) return 1;
  fflush(st->wal);
#ifdef __unix__
  fsync(fileno(st->wal));
#endif
  return 0;
}

}  // namespace

extern "C" {

void* kb_open() { return new Store(); }

// Durable open: load snapshot + replay WAL from dir, then append new commits
// to the WAL (fsync per commit when fsync_commits != 0).
void* kb_open_at(const char* dir, int fsync_commits) {
  Store* st = new Store();
  if (dir != nullptr && dir[0] != '\0') {
    st->dir = dir;
    st->fsync_commits = fsync_commits != 0;
    replay_file(st, st->dir + "/snapshot.kb");
    uint64_t snap_ts = st->ts;
    // skip WAL records the snapshot already covers (a crash between the
    // snapshot rename and the WAL truncation leaves them behind)
    replay_file(st, st->dir + "/wal.kb", snap_ts);
    // checkpoint immediately: writes a clean snapshot and truncates the WAL,
    // so a torn tail left by a crash is never appended after
    if (checkpoint_locked(st) != 0) {
      delete st;  // ~Store closes the WAL handle if one was opened
      return nullptr;
    }
  }
  return st;
}

int kb_checkpoint(void* s) {
  Store* st = static_cast<Store*>(s);
  if (st->dir.empty()) return 0;
  std::unique_lock<std::shared_mutex> lock(st->mu);
  return checkpoint_locked(st);
}

void kb_close(void* s) {
  Store* st = static_cast<Store*>(s);
  if (!st->dir.empty()) {
    std::unique_lock<std::shared_mutex> lock(st->mu);
    checkpoint_locked(st);
    if (st->wal != nullptr) {
      fclose(st->wal);
      st->wal = nullptr;
    }
  }
  delete st;
}

uint64_t kb_tso(void* s) {
  Store* st = static_cast<Store*>(s);
  std::shared_lock<std::shared_mutex> lock(st->mu);
  return st->ts;
}

// ------------------------------------------------------------- replication
// (kbstored's WAL-shipping follower tier; the raft-replication role of the
// reference's TiKV layer, tikv.go:123-153.)

void kb_set_commit_hook(void* s, kb_commit_cb cb, void* ctx) {
  Store* st = static_cast<Store*>(s);
  std::unique_lock<std::shared_mutex> lock(st->mu);
  st->hook = cb;
  st->hook_ctx = ctx;
}

// Apply one serialized WAL record received from a replication stream.
// reset=1 clears existing state first (full-dump bootstrap) and writes a
// fresh snapshot so pre-dump keys can never resurface from this store's own
// older snapshot on restart. Idempotent: records at or below the current
// clock are skipped (rc 3). rc: 0 applied, 1 malformed, 2 wal/checkpoint
// failure, 3 stale/duplicate. *applied_ts is the store clock after the call.
int kb_apply_record(void* s, const uint8_t* rec, size_t len, int reset,
                    uint64_t* applied_ts) {
  Store* st = static_cast<Store*>(s);
  // parse (bounds-checked) before taking the lock
  if (len < 16) return 1;
  uint32_t magic, nops;
  uint64_t ts;
  memcpy(&magic, rec, 4);
  memcpy(&ts, rec + 4, 8);
  memcpy(&nops, rec + 12, 4);
  if (magic != kWalMagic) return 1;
  if (nops > (len - 16) / 17) return 1;  // cheap bound before reserve
  size_t off = 16;
  std::vector<AppliedOp> ops;
  ops.reserve(nops);
  for (uint32_t i = 0; i < nops; ++i) {
    if (off + 17 > len) return 1;
    AppliedOp op;
    uint32_t klen, vlen;
    op.kind = rec[off];
    memcpy(&klen, rec + off + 1, 4);
    memcpy(&vlen, rec + off + 5, 4);
    memcpy(&op.expire_at, rec + off + 9, 8);
    off += 17;
    if (off + static_cast<size_t>(klen) + vlen > len) return 1;
    op.key.assign(reinterpret_cast<const char*>(rec + off), klen);
    off += klen;
    op.value.assign(reinterpret_cast<const char*>(rec + off), vlen);
    off += vlen;
    ops.push_back(std::move(op));
  }

  std::unique_lock<std::shared_mutex> lock(st->mu);
  if (!reset && ts <= st->ts) {
    if (applied_ts != nullptr) *applied_ts = st->ts;
    return 3;
  }
  if (reset) {
    st->data.clear();
    st->ts = 0;
  } else {
    // stream records go through this store's own WAL first (same
    // durability contract as a local commit)
    std::string raw(reinterpret_cast<const char*>(rec), len);
    if (!append_wal_raw(st, raw)) return 2;
  }
  for (const AppliedOp& a : ops) {
    Version v;
    v.ts = ts;
    v.deleted = a.kind == 1;
    v.expire_at = a.expire_at;
    v.value = a.value;
    st->data[a.key].push_back(std::move(v));
  }
  st->ts = ts;
  if (reset && !st->dir.empty()) {
    // the dump is durable only through this checkpoint (the reset path
    // skips the WAL). On failure, roll the store back to empty/ts=0 so a
    // reconnect HELLO carries fts=0 and the primary re-ships the dump —
    // otherwise the follower would ack a lineage it can lose on restart.
    if (checkpoint_locked(st) != 0) {
      st->data.clear();
      st->ts = 0;
      if (applied_ts != nullptr) *applied_ts = 0;
      return 2;
    }
  }
  if (applied_ts != nullptr) *applied_ts = st->ts;
  return 0;
}

// Serialize the latest-only live state as ONE wal record at the current
// clock (the follower-bootstrap dump — same shape checkpoint_locked
// persists). Caller frees *out with kb_free.
int kb_dump_wire(void* s, uint8_t** out, size_t* out_len, uint64_t* ts_out) {
  Store* st = static_cast<Store*>(s);
  std::shared_lock<std::shared_mutex> lock(st->mu);
  double now = wallclock();
  std::vector<AppliedOp> ops;
  ops.reserve(st->data.size());
  for (const auto& entry : st->data) {
    const std::string* v = st->live(entry.first, st->ts, now);
    if (v == nullptr) continue;
    AppliedOp op;
    op.kind = 0;
    op.key = entry.first;
    op.value = *v;
    op.expire_at = entry.second.back().expire_at;
    ops.push_back(std::move(op));
  }
  std::string rec;
  serialize_record(rec, st->ts, ops);
  *out = static_cast<uint8_t*>(malloc(rec.size()));
  if (*out == nullptr) return 1;
  memcpy(*out, rec.data(), rec.size());
  *out_len = rec.size();
  *ts_out = st->ts;
  return 0;
}

// Point get at a snapshot (snap = 0 means latest). Returns 0 and copies the
// value into a malloc'd buffer on hit; 1 on miss.
int kb_get(void* s, const uint8_t* key, size_t klen, uint64_t snap,
           uint8_t** out, size_t* out_len) {
  Store* st = static_cast<Store*>(s);
  std::shared_lock<std::shared_mutex> lock(st->mu);
  std::string k(reinterpret_cast<const char*>(key), klen);
  const std::string* v = st->live(k, snap ? snap : st->ts, wallclock());
  if (v == nullptr) return 1;
  *out = static_cast<uint8_t*>(malloc(v->size()));
  memcpy(*out, v->data(), v->size());
  *out_len = v->size();
  return 0;
}

void kb_free(void* p) { free(p); }

// ------------------------------------------------------------------ batches
void* kb_batch_begin(void* s) {
  Batch* b = new Batch();
  b->store = static_cast<Store*>(s);
  return b;
}

static void push_op(void* b, int kind, const uint8_t* key, size_t klen,
                    const uint8_t* val, size_t vlen, const uint8_t* exp,
                    size_t elen, int64_t ttl) {
  Batch* batch = static_cast<Batch*>(b);
  Op op;
  op.kind = kind;
  op.key.assign(reinterpret_cast<const char*>(key), klen);
  if (val) op.value.assign(reinterpret_cast<const char*>(val), vlen);
  if (exp) op.expected.assign(reinterpret_cast<const char*>(exp), elen);
  op.ttl_seconds = ttl;
  batch->ops.push_back(std::move(op));
}

void kb_batch_put(void* b, const uint8_t* k, size_t kl, const uint8_t* v,
                  size_t vl, int64_t ttl) {
  push_op(b, OP_PUT, k, kl, v, vl, nullptr, 0, ttl);
}

void kb_batch_put_if_absent(void* b, const uint8_t* k, size_t kl,
                            const uint8_t* v, size_t vl, int64_t ttl) {
  push_op(b, OP_PUT_IF_ABSENT, k, kl, v, vl, nullptr, 0, ttl);
}

void kb_batch_cas(void* b, const uint8_t* k, size_t kl, const uint8_t* nv,
                  size_t nvl, const uint8_t* ov, size_t ovl, int64_t ttl) {
  push_op(b, OP_CAS, k, kl, nv, nvl, ov, ovl, ttl);
}

void kb_batch_del(void* b, const uint8_t* k, size_t kl) {
  push_op(b, OP_DEL, k, kl, nullptr, 0, nullptr, 0, 0);
}

void kb_batch_del_current(void* b, const uint8_t* k, size_t kl,
                          const uint8_t* exp, size_t el) {
  push_op(b, OP_DEL_CURRENT, k, kl, nullptr, 0, exp, el, 0);
}

void kb_batch_abort(void* b) { delete static_cast<Batch*>(b); }

// Commit: all-or-nothing under the writer lock. Returns 0 on success; 1 on
// conditional-op conflict, filling conflict_idx and (when the key had a live
// value) a malloc'd copy of the observed value (conflict_has_val = 1).
// The batch is freed either way.
int kb_batch_commit(void* b, int64_t* conflict_idx, uint8_t** conflict_val,
                    size_t* conflict_len, int* conflict_has_val) {
  std::unique_ptr<Batch> batch(static_cast<Batch*>(b));
  Store* st = batch->store;
  double now = wallclock();
  std::unique_lock<std::shared_mutex> lock(st->mu);
  // validate conditions against latest state
  for (size_t i = 0; i < batch->ops.size(); ++i) {
    const Op& op = batch->ops[i];
    if (op.kind == OP_PUT || op.kind == OP_DEL) continue;
    const std::string* cur = st->live(op.key, st->ts, now);
    bool ok = true;
    if (op.kind == OP_PUT_IF_ABSENT) {
      ok = (cur == nullptr);
    } else if (op.kind == OP_CAS || op.kind == OP_DEL_CURRENT) {
      ok = (cur != nullptr && *cur == op.expected);
    }
    if (!ok) {
      *conflict_idx = static_cast<int64_t>(i);
      if (cur != nullptr) {
        *conflict_val = static_cast<uint8_t*>(malloc(cur->size()));
        memcpy(*conflict_val, cur->data(), cur->size());
        *conflict_len = cur->size();
        *conflict_has_val = 1;
      } else {
        *conflict_has_val = 0;
      }
      return 1;
    }
  }
  uint64_t ts = ++st->ts;
  std::vector<AppliedOp> applied;
  applied.reserve(batch->ops.size());
  for (const Op& op : batch->ops) {
    AppliedOp a;
    a.key = op.key;
    if (op.kind == OP_DEL || op.kind == OP_DEL_CURRENT) {
      a.kind = 1;
      a.expire_at = 0;
    } else {
      a.kind = 0;
      a.expire_at = op.ttl_seconds ? now + static_cast<double>(op.ttl_seconds) : 0;
      a.value = op.value;
    }
    applied.push_back(std::move(a));
  }
  // write-ahead: the record hits the log before memory state mutates; a
  // failed append rolls the log back to the record start and FAILS the
  // commit (rc 2) — an acknowledged write must be replayable
  std::string rec;
  if (!log_commit(st, ts, applied, &rec)) {
    --st->ts;  // the failed commit's timestamp was never observable
    return 2;
  }
  for (const AppliedOp& a : applied) {
    Version v;
    v.ts = ts;
    v.deleted = a.kind == 1;
    v.expire_at = a.expire_at;
    v.value = a.value;
    st->data[a.key].push_back(std::move(v));
  }
  fire_hook(st, rec, ts);
  return 0;
}

// Bulk MVCC garbage collection — the compaction fast path. Deletes
// n_victims object rows (internal key = magic + user_key + \x00 + be64(rev))
// and conditionally deletes n_recs revision records (internal key at rev 0)
// whose CURRENT value still equals the expected rev-record bytes
// (be64(last_rev) [+ 0x01 when tombstoned]) — the del_current guard of
// scanner.go:477-491, vectorized. Everything lands in ONE lock acquisition
// and ONE WAL record, so a million-victim sweep costs no per-row Python and
// no per-row commit. Keys arrive as fixed-width rows (width) + lengths.
// Returns the number of revision records deleted; object-row deletes are
// unconditional. rc via out-param style is unnecessary: WAL failure returns
// UINT64_MAX.
uint64_t kb_bulk_gc(void* s,
                    const uint8_t* vkeys, const int32_t* vlens,
                    const uint64_t* vrevs, uint64_t n_victims,
                    const uint8_t* rkeys, const int32_t* rlens,
                    const uint64_t* rrevs, const uint8_t* rtomb,
                    uint64_t n_recs, size_t width,
                    const uint8_t* magic, size_t magic_len) {
  Store* st = static_cast<Store*>(s);
  double now = wallclock();
  std::string mg(reinterpret_cast<const char*>(magic), magic_len);
  auto internal_key = [&](const uint8_t* rows, const int32_t* lens,
                          uint64_t i, uint64_t rev) {
    std::string k = mg;
    k.append(reinterpret_cast<const char*>(rows + i * width),
             static_cast<size_t>(lens[i]));
    k.push_back('\0');
    for (int b = 7; b >= 0; --b)
      k.push_back(static_cast<char>((rev >> (8 * b)) & 0xFF));
    return k;
  };

  std::unique_lock<std::shared_mutex> lock(st->mu);
  std::vector<AppliedOp> applied;
  applied.reserve(n_victims + n_recs);
  for (uint64_t i = 0; i < n_victims; ++i) {
    AppliedOp a;
    a.kind = 1;
    a.expire_at = 0;
    a.key = internal_key(vkeys, vlens, i, vrevs[i]);
    applied.push_back(std::move(a));
  }
  uint64_t rec_deleted = 0;
  for (uint64_t i = 0; i < n_recs; ++i) {
    std::string rk = internal_key(rkeys, rlens, i, 0);
    std::string expect;
    for (int b = 7; b >= 0; --b)
      expect.push_back(static_cast<char>((rrevs[i] >> (8 * b)) & 0xFF));
    if (rtomb[i]) expect.push_back('\x01');
    const std::string* cur = st->live(rk, st->ts, now);
    if (cur == nullptr || *cur != expect) continue;  // rewritten since
    AppliedOp a;
    a.kind = 1;
    a.expire_at = 0;
    a.key = std::move(rk);
    applied.push_back(std::move(a));
    ++rec_deleted;
  }
  if (applied.empty()) return 0;
  uint64_t ts = ++st->ts;
  std::string rec;
  if (!log_commit(st, ts, applied, &rec)) {
    --st->ts;
    return UINT64_MAX;
  }
  for (const AppliedOp& a : applied) {
    Version v;
    v.ts = ts;
    v.deleted = true;
    v.expire_at = 0;
    st->data[a.key].push_back(std::move(v));
  }
  fire_hook(st, rec, ts);
  return rec_deleted;
}

// --------------------------------------------------------------- iteration
// Snapshot range iterator, buffered at open (consistent view without holding
// the lock across the drain). Forward: [start, end) ascending; reverse
// (reverse=1): [end, start] descending — the engine-contract shape the
// backend's point-get path expects.
void* kb_iter_open(void* s, const uint8_t* start, size_t slen,
                   const uint8_t* end, size_t elen, uint64_t snap,
                   uint64_t limit, int reverse) {
  Store* st = static_cast<Store*>(s);
  std::string lo(reinterpret_cast<const char*>(start), slen);
  std::string hi(reinterpret_cast<const char*>(end), elen);
  Iter* it = new Iter();
  double now = wallclock();
  std::shared_lock<std::shared_mutex> lock(st->mu);
  uint64_t at = snap ? snap : st->ts;
  if (!reverse) {
    auto b = st->data.lower_bound(lo);
    auto e = hi.empty() ? st->data.end() : st->data.lower_bound(hi);
    for (auto cur = b; cur != e; ++cur) {
      const std::string* v = st->live(cur->first, at, now);
      if (v == nullptr) continue;
      it->buf.emplace_back(cur->first, *v);
      if (limit && it->buf.size() >= limit) break;
    }
  } else {
    // reverse contract: keys k with hi <= k <= lo, descending (lo=start)
    auto b = st->data.lower_bound(hi);
    auto e = st->data.upper_bound(lo);
    for (auto cur = e; cur != b;) {
      --cur;
      const std::string* v = st->live(cur->first, at, now);
      if (v == nullptr) continue;
      it->buf.emplace_back(cur->first, *v);
      if (limit && it->buf.size() >= limit) break;
    }
  }
  return it;
}

int kb_iter_next(void* itp, const uint8_t** key, size_t* klen,
                 const uint8_t** val, size_t* vlen) {
  Iter* it = static_cast<Iter*>(itp);
  if (it->pos >= it->buf.size()) return 1;
  const auto& kv = it->buf[it->pos++];
  *key = reinterpret_cast<const uint8_t*>(kv.first.data());
  *klen = kv.first.size();
  *val = reinterpret_cast<const uint8_t*>(kv.second.data());
  *vlen = kv.second.size();
  return 0;
}

void kb_iter_close(void* itp) { delete static_cast<Iter*>(itp); }

// ------------------------------------------------------------- partitions
// Sample n_parts-1 evenly spaced live keys as split borders (the shard map
// the reference gets from PD ScanRegions, pkg/storage/tikv/tikv.go:123-153).
// Borders are written into caller-provided fixed-width rows; returns the
// number of borders produced.
int kb_split_keys(void* s, int n_parts, uint8_t* borders, size_t row_width,
                  size_t* border_lens) {
  Store* st = static_cast<Store*>(s);
  std::shared_lock<std::shared_mutex> lock(st->mu);
  size_t n = st->data.size();
  if (n_parts <= 1 || n < static_cast<size_t>(n_parts)) return 0;
  size_t stride = n / static_cast<size_t>(n_parts);
  int produced = 0;
  size_t i = 0;
  for (const auto& entry : st->data) {
    if (produced >= n_parts - 1) break;
    if (i > 0 && i % stride == 0) {
      size_t copy = entry.first.size() < row_width ? entry.first.size() : row_width;
      memcpy(borders + static_cast<size_t>(produced) * row_width,
             entry.first.data(), copy);
      border_lens[produced] = copy;
      ++produced;
    }
    ++i;
  }
  return produced;
}

uint64_t kb_key_count(void* s) {
  Store* st = static_cast<Store*>(s);
  std::shared_lock<std::shared_mutex> lock(st->mu);
  return st->data.size();
}

uint64_t kb_version_count(void* s) {
  Store* st = static_cast<Store*>(s);
  std::shared_lock<std::shared_mutex> lock(st->mu);
  uint64_t n = 0;
  for (const auto& e : st->data) n += e.second.size();
  return n;
}

// Physically free version-chain history: for every key, drop versions
// superseded before ``keep_after_ts`` (invisible to any snapshot >=
// keep_after_ts) and erase keys whose only remaining state is a deletion at
// or before it. Safe because engine snapshots are consumed synchronously
// under the store lock (iterators buffer at open), so no reader can hold a
// snapshot older than the writer-lock acquisition here. Returns versions
// freed. (MVCC-layer compaction issues logical deletes; without this the
// version vectors grow forever on a long-running server.)
uint64_t kb_prune(void* s, uint64_t keep_after_ts) {
  Store* st = static_cast<Store*>(s);
  std::unique_lock<std::shared_mutex> lock(st->mu);
  double now = wallclock();
  uint64_t freed = 0;
  for (auto it = st->data.begin(); it != st->data.end();) {
    auto& versions = it->second;
    // newest version with ts <= keep_after_ts: everything older is invisible
    size_t last_visible = versions.size();
    for (size_t i = 0; i < versions.size(); ++i) {
      if (versions[i].ts <= keep_after_ts) last_visible = i;
    }
    if (last_visible != versions.size() && last_visible > 0) {
      versions.erase(versions.begin(), versions.begin() + last_visible);
      freed += last_visible;
    }
    // fully-dead key: single remaining version is a delete/expired at cutoff
    bool dead = true;
    for (const auto& v : versions) {
      if (v.ts > keep_after_ts) { dead = false; break; }
      if (!v.deleted && !(v.expire_at != 0 && now >= v.expire_at)) { dead = false; break; }
    }
    if (dead && !versions.empty()) {
      freed += versions.size();
      it = st->data.erase(it);
    } else {
      ++it;
    }
  }
  return freed;
}

// ------------------------------------------------------------- MVCC write
// The hot write path as ONE native call (conditional revision-record write +
// object row + last-revision watermark, atomically): the Python MVCC layer
// otherwise pays five FFI crossings per write. Returns 0 ok; 1 conflict
// (conflict_val filled when the record exists); 2 WAL append failure.
int kb_mvcc_write(void* s,
                  const uint8_t* rev_key, size_t rkl,
                  const uint8_t* rev_val, size_t rvl,
                  const uint8_t* expected, size_t el, int has_expected,
                  const uint8_t* obj_key, size_t okl,
                  const uint8_t* obj_val, size_t ovl,
                  const uint8_t* last_key, size_t lkl,
                  const uint8_t* last_val, size_t lvl,
                  int64_t ttl,
                  uint8_t** conflict_val, size_t* conflict_len,
                  int* conflict_has) {
  Store* st = static_cast<Store*>(s);
  double now = wallclock();
  std::string rk(reinterpret_cast<const char*>(rev_key), rkl);
  std::unique_lock<std::shared_mutex> lock(st->mu);
  const std::string* cur = st->live(rk, st->ts, now);
  bool ok;
  if (has_expected) {
    std::string exp(reinterpret_cast<const char*>(expected), el);
    ok = (cur != nullptr && *cur == exp);
  } else {
    ok = (cur == nullptr);
  }
  if (!ok) {
    if (cur != nullptr) {
      *conflict_val = static_cast<uint8_t*>(malloc(cur->size()));
      memcpy(*conflict_val, cur->data(), cur->size());
      *conflict_len = cur->size();
      *conflict_has = 1;
    } else {
      *conflict_has = 0;
    }
    return 1;
  }
  uint64_t ts = ++st->ts;
  double expire = ttl ? now + static_cast<double>(ttl) : 0;
  std::vector<AppliedOp> applied(3);
  applied[0].kind = 0;
  applied[0].key = rk;
  applied[0].value.assign(reinterpret_cast<const char*>(rev_val), rvl);
  applied[0].expire_at = expire;
  applied[1].kind = 0;
  applied[1].key.assign(reinterpret_cast<const char*>(obj_key), okl);
  applied[1].value.assign(reinterpret_cast<const char*>(obj_val), ovl);
  applied[1].expire_at = expire;
  applied[2].kind = 0;
  applied[2].key.assign(reinterpret_cast<const char*>(last_key), lkl);
  applied[2].value.assign(reinterpret_cast<const char*>(last_val), lvl);
  applied[2].expire_at = 0;
  std::string rec;
  if (!log_commit(st, ts, applied, &rec)) {
    --st->ts;
    return 2;
  }
  for (AppliedOp& a : applied) {
    Version v;
    v.ts = ts;
    v.deleted = false;
    v.expire_at = a.expire_at;
    v.value = std::move(a.value);
    st->data[a.key].push_back(std::move(v));
  }
  fire_hook(st, rec, ts);
  return 0;
}

// ------------------------------------------------------------ MVCC delete
// The reference's documented weakness is the delete path: a read of the
// revision record, a read of the previous value, then a CAS batch — three
// engine round-trips (txn.go:145-190; benchmark.md "delete needs
// optimization"). Here the whole read-validate-write sequence is ONE native
// call under one lock. Outcomes: 0 ok (prev value + revision returned);
// 1 key absent/already deleted; 2 revision mismatch (latest returned);
// 3 WAL failure; 4 revision drift (new_rev <= latest).
int kb_mvcc_delete(void* s,
                   const uint8_t* rev_key, size_t rkl,
                   uint64_t expected_rev,  // 0 = unconditional
                   uint64_t new_rev,
                   const uint8_t* new_record, size_t nrl,
                   const uint8_t* tombstone, size_t tl,
                   const uint8_t* last_key, size_t lkl,
                   const uint8_t* last_val, size_t lvl,
                   uint8_t** prev_val, size_t* prev_len,
                   uint64_t* latest_rev_out) {
  Store* st = static_cast<Store*>(s);
  double now = wallclock();
  std::string rk(reinterpret_cast<const char*>(rev_key), rkl);
  std::unique_lock<std::shared_mutex> lock(st->mu);
  *latest_rev_out = 0;
  const std::string* record = st->live(rk, st->ts, now);
  if (record == nullptr) return 1;  // truly absent: latest stays 0
  if (record->size() == 9) {
    // deleted: report the tombstone's revision so the caller can fence its
    // read floor precisely (backend _await_revealed) instead of syncing to
    // the global watermark
    uint64_t latest = 0;
    for (int i = 0; i < 8; ++i) {
      latest = (latest << 8) | static_cast<uint8_t>((*record)[i]);
    }
    *latest_rev_out = latest;
    return 1;
  }
  if (record->size() != 8) return 1;
  uint64_t latest = 0;
  for (int i = 0; i < 8; ++i) {
    latest = (latest << 8) | static_cast<uint8_t>((*record)[i]);
  }
  *latest_rev_out = latest;
  // previous object row: rev_key with the trailing revision replaced
  std::string obj_old = rk;
  for (int i = 0; i < 8; ++i) {
    obj_old[rkl - 8 + i] = static_cast<char>((latest >> (8 * (7 - i))) & 0xFF);
  }
  const std::string* prev = st->live(obj_old, st->ts, now);
  if (prev != nullptr && !prev->empty()) {
    // empty previous values stay {nullptr, 0}: the python adapter frees on
    // prev_len truthiness, so a malloc(0) here would leak
    *prev_val = static_cast<uint8_t*>(malloc(prev->size()));
    memcpy(*prev_val, prev->data(), prev->size());
    *prev_len = prev->size();
  } else {
    *prev_len = 0;
    *prev_val = nullptr;
  }
  if (expected_rev != 0 && latest != expected_rev) return 2;
  if (new_rev <= latest) return 4;
  std::string obj_new = rk;
  for (int i = 0; i < 8; ++i) {
    obj_new[rkl - 8 + i] = static_cast<char>((new_rev >> (8 * (7 - i))) & 0xFF);
  }
  uint64_t ts = ++st->ts;
  std::vector<AppliedOp> applied(3);
  applied[0].kind = 0;
  applied[0].key = rk;
  applied[0].value.assign(reinterpret_cast<const char*>(new_record), nrl);
  applied[0].expire_at = 0;
  applied[1].kind = 0;
  applied[1].key = obj_new;
  applied[1].value.assign(reinterpret_cast<const char*>(tombstone), tl);
  applied[1].expire_at = 0;
  applied[2].kind = 0;
  applied[2].key.assign(reinterpret_cast<const char*>(last_key), lkl);
  applied[2].value.assign(reinterpret_cast<const char*>(last_val), lvl);
  applied[2].expire_at = 0;
  std::string rec;
  if (!log_commit(st, ts, applied, &rec)) {
    --st->ts;
    return 3;
  }
  for (AppliedOp& a : applied) {
    Version v;
    v.ts = ts;
    v.deleted = false;
    v.expire_at = a.expire_at;
    v.value = std::move(a.value);
    st->data[a.key].push_back(std::move(v));
  }
  fire_hook(st, rec, ts);
  return 0;
}

// ------------------------------------------------------- MVCC bulk export
// Host-shim fast path for the TPU mirror (SURVEY §2.8): walk the MVCC
// internal keyspace (magic + user_key + NUL + big-endian u64 revision) at a
// snapshot and fill caller-provided numpy-ready buffers — padded user keys,
// lengths, revisions, tombstone flags, and a value arena with offsets — so
// mirror rebuilds never round-trip per row through Python.

static bool parse_internal(const std::string& k, const uint8_t* magic,
                           size_t magic_len, size_t* key_len, uint64_t* rev) {
  if (k.size() < magic_len + 1 + 8 + 1) return false;
  if (memcmp(k.data(), magic, magic_len) != 0) return false;
  if (static_cast<uint8_t>(k[k.size() - 9]) != 0) return false;
  *key_len = k.size() - magic_len - 9;
  uint64_t r = 0;
  for (int i = 0; i < 8; ++i) {
    r = (r << 8) | static_cast<uint8_t>(k[k.size() - 8 + i]);
  }
  *rev = r;
  return true;
}

// Pass 1: count version rows and total value bytes in [start, end) at snap.
void kb_mvcc_export_stats(void* s, const uint8_t* start, size_t slen,
                          const uint8_t* end, size_t elen, uint64_t snap,
                          const uint8_t* magic, size_t magic_len,
                          uint64_t* n_rows, uint64_t* val_bytes) {
  Store* st = static_cast<Store*>(s);
  std::shared_lock<std::shared_mutex> lock(st->mu);
  uint64_t at = snap ? snap : st->ts;
  double now = wallclock();
  std::string lo(reinterpret_cast<const char*>(start), slen);
  std::string hi(reinterpret_cast<const char*>(end), elen);
  *n_rows = 0;
  *val_bytes = 0;
  auto b = st->data.lower_bound(lo);
  auto e = hi.empty() ? st->data.end() : st->data.lower_bound(hi);
  for (auto cur = b; cur != e; ++cur) {
    size_t klen;
    uint64_t rev;
    if (!parse_internal(cur->first, magic, magic_len, &klen, &rev)) continue;
    if (rev == 0) continue;
    const std::string* v = st->live(cur->first, at, now);
    if (v == nullptr) continue;
    ++*n_rows;
    *val_bytes += v->size();
  }
}

// Pass 2: fill buffers sized from pass 1. keys_buf is n_rows * key_width
// zero-initialized by the caller; keys longer than key_width are rejected
// (returns the number of rows written, or UINT64_MAX on overflow).
uint64_t kb_mvcc_export_fill(void* s, const uint8_t* start, size_t slen,
                             const uint8_t* end, size_t elen, uint64_t snap,
                             const uint8_t* magic, size_t magic_len,
                             const uint8_t* tombstone, size_t tomb_len,
                             size_t key_width, uint64_t max_rows,
                             uint8_t* keys_buf, int32_t* lens_buf,
                             uint64_t* revs_buf, uint8_t* tomb_buf,
                             uint8_t* val_arena, uint64_t* val_offsets) {
  Store* st = static_cast<Store*>(s);
  std::shared_lock<std::shared_mutex> lock(st->mu);
  uint64_t at = snap ? snap : st->ts;
  double now = wallclock();
  std::string lo(reinterpret_cast<const char*>(start), slen);
  std::string hi(reinterpret_cast<const char*>(end), elen);
  std::string tomb(reinterpret_cast<const char*>(tombstone), tomb_len);
  uint64_t row = 0, off = 0;
  val_offsets[0] = 0;
  auto b = st->data.lower_bound(lo);
  auto e = hi.empty() ? st->data.end() : st->data.lower_bound(hi);
  for (auto cur = b; cur != e; ++cur) {
    size_t klen;
    uint64_t rev;
    if (!parse_internal(cur->first, magic, magic_len, &klen, &rev)) continue;
    if (rev == 0) continue;
    const std::string* v = st->live(cur->first, at, now);
    if (v == nullptr) continue;
    if (row >= max_rows || klen > key_width) return UINT64_MAX;
    memcpy(keys_buf + row * key_width, cur->first.data() + magic_len, klen);
    lens_buf[row] = static_cast<int32_t>(klen);
    revs_buf[row] = rev;
    tomb_buf[row] = (*v == tomb) ? 1 : 0;
    memcpy(val_arena + off, v->data(), v->size());
    off += v->size();
    val_offsets[row + 1] = off;
    ++row;
  }
  return row;
}

// One forward-scan page in a single FFI call: fills caller-provided key and
// value arenas + offset arrays with up to max_rows live rows of [start, end)
// at `snap`. Row-at-a-time ctypes iteration costs ~8us/row in Python (3
// calls + 2 copies + 4 byrefs per row); this turns a 1000-row page into one
// call. Stops early (sets *more=1) when a cap would overflow; the caller
// resumes from its last key + '\0'. Returns rows written. A first row too
// big for the caps also reports more=1 with 0 rows — caller must grow the
// value arena.
uint64_t kb_scan_page(void* s, const uint8_t* start, size_t slen,
                      const uint8_t* end, size_t elen, uint64_t snap,
                      uint64_t max_rows, uint8_t* key_arena, uint64_t key_cap,
                      uint64_t* key_offs, uint8_t* val_arena, uint64_t val_cap,
                      uint64_t* val_offs, int* more) {
  Store* st = static_cast<Store*>(s);
  std::shared_lock<std::shared_mutex> lock(st->mu);
  uint64_t at = snap ? snap : st->ts;
  double now = wallclock();
  std::string lo(reinterpret_cast<const char*>(start), slen);
  std::string hi(reinterpret_cast<const char*>(end), elen);
  uint64_t row = 0, koff = 0, voff = 0;
  key_offs[0] = 0;
  val_offs[0] = 0;
  *more = 0;
  auto b = st->data.lower_bound(lo);
  auto e = hi.empty() ? st->data.end() : st->data.lower_bound(hi);
  for (auto cur = b; cur != e; ++cur) {
    const std::string* v = st->live(cur->first, at, now);
    if (v == nullptr) continue;
    if (row >= max_rows || koff + cur->first.size() > key_cap ||
        voff + v->size() > val_cap) {
      *more = 1;
      break;
    }
    memcpy(key_arena + koff, cur->first.data(), cur->first.size());
    koff += cur->first.size();
    key_offs[row + 1] = koff;
    memcpy(val_arena + voff, v->data(), v->size());
    voff += v->size();
    val_offs[row + 1] = voff;
    ++row;
  }
  return row;
}

// The MVCC list pass, shared by the arena-page (FFI) and wire-page
// (protobuf bytes) emitters. The rule is the reference scan worker's single
// pass ("last version <= read_rev per user key, tombstones suppressed",
// scanner.go:389-516). Pages never split a user key's version chain: when
// the emitter reports full at a key boundary, resume_raw is that key's
// first raw row and *more is set. Templates cannot take C linkage, so the
// extern "C" block closes around the helper.
}  // extern "C"

template <typename Emit>
static uint64_t mvcc_list_walk(Store* st, const std::string& lo,
                               const std::string& hi, uint64_t at, double now,
                               uint64_t read_rev, const uint8_t* magic,
                               size_t magic_len, const std::string& tomb,
                               Emit emit, std::string* resume_raw, int* more) {
  uint64_t rows = 0;
  *more = 0;
  resume_raw->clear();

  bool pend = false;
  const char* pk = nullptr;  // user-key bytes (stable std::map node storage)
  size_t pklen = 0;
  uint64_t prev_rev = 0;
  const std::string* pval = nullptr;
  std::string pend_first_raw;  // first raw row of the pending user key

  auto flush = [&]() -> int {  // 0 ok (emitted or skipped), 1 caps full
    if (!pend) return 0;
    pend = false;
    if (pval->size() == tomb.size() &&
        memcmp(pval->data(), tomb.data(), tomb.size()) == 0)
      return 0;  // tombstoned at read_rev
    if (!emit(pk, pklen, *pval, prev_rev)) return 1;
    ++rows;
    return 0;
  };

  auto b = st->data.lower_bound(lo);
  auto e = hi.empty() ? st->data.end() : st->data.lower_bound(hi);
  for (auto cur = b; cur != e; ++cur) {
    size_t klen;
    uint64_t rev;
    if (!parse_internal(cur->first, magic, magic_len, &klen, &rev)) continue;
    if (rev == 0) continue;
    const char* ukey = cur->first.data() + magic_len;
    bool same = pend && klen == pklen && memcmp(ukey, pk, klen) == 0;
    if (!same) {
      std::string first_raw_of_new = cur->first;
      if (flush() != 0) {
        // caps hit: resume from the pending key's first raw row (it was
        // consumed but not emitted)
        *resume_raw = pend_first_raw;
        *more = 1;
        return rows;
      }
      pend_first_raw = std::move(first_raw_of_new);
      pk = nullptr;
      pklen = 0;
    }
    const std::string* v = st->live(cur->first, at, now);
    if (v == nullptr) continue;
    if (rev <= read_rev) {
      // ascending revision order within a key: later rows overwrite
      pend = true;
      pk = ukey;
      pklen = klen;
      prev_rev = rev;
      pval = v;
    }
  }
  if (flush() != 0) {
    *resume_raw = pend_first_raw;
    *more = 1;
  }
  return rows;
}

static inline size_t varint_len(uint64_t v) {
  size_t n = 1;
  while (v >= 0x80) {
    v >>= 7;
    ++n;
  }
  return n;
}

static inline uint8_t* put_varint(uint8_t* p, uint64_t v) {
  while (v >= 0x80) {
    *p++ = static_cast<uint8_t>(v | 0x80);
    v >>= 7;
  }
  *p++ = static_cast<uint8_t>(v);
  return p;
}

// THE wire layout of one list row: one `repeated KeyValue kvs = 2` element
// of an etcd RangeResponse (mvccpb: key=1, create_revision=2,
// mod_revision=3, version=4, value=5; create = mod = rev, version = 1 —
// what the python shim's to_kv builds). kb_mvcc_list_wire (the store's own
// scan) and kb_wire_gather (rows of the device mirror's host arrays) both
// encode through these two functions and nothing else does.
static inline size_t wire_row_body(size_t kl, size_t vl, uint64_t rev) {
  return 1 + varint_len(kl) + kl + 1 + varint_len(vl) + vl +
         2 * (1 + varint_len(rev)) + 2;
}

static inline size_t wire_row_size(size_t kl, size_t vl, uint64_t rev) {
  size_t body = wire_row_body(kl, vl, rev);
  return 1 + varint_len(body) + body;
}

// Writes exactly wire_row_size(kl, vl, rev) bytes at p; returns the end.
static inline uint8_t* wire_put_row(uint8_t* p, const void* k, size_t kl,
                                    const void* v, size_t vl, uint64_t rev) {
  *p++ = 0x12;  // RangeResponse.kvs
  p = put_varint(p, wire_row_body(kl, vl, rev));
  *p++ = 0x0A;  // KeyValue.key
  p = put_varint(p, kl);
  if (kl) memcpy(p, k, kl);
  p += kl;
  *p++ = 0x10;  // create_revision
  p = put_varint(p, rev);
  *p++ = 0x18;  // mod_revision
  p = put_varint(p, rev);
  *p++ = 0x20;  // version
  *p++ = 1;
  *p++ = 0x2A;  // value
  p = put_varint(p, vl);
  if (vl) memcpy(p, v, vl);
  return p + vl;
}

extern "C" {

// One MVCC list page in a single FFI call — visible (user_key, value,
// revision) triples into caller arenas. Returns rows written; 0 rows with
// more=1 means the first visible row cannot fit the caps (caller must grow
// the value arena and retry from the same cursor).
uint64_t kb_mvcc_list_page(void* s, const uint8_t* start, size_t slen,
                           const uint8_t* end, size_t elen, uint64_t snap,
                           uint64_t read_rev, const uint8_t* magic,
                           size_t magic_len, const uint8_t* tombstone,
                           size_t tomb_len, uint64_t max_rows,
                           uint8_t* key_arena, uint64_t key_cap,
                           uint64_t* key_offs, uint8_t* val_arena,
                           uint64_t val_cap, uint64_t* val_offs,
                           uint64_t* revs_out, uint8_t* next_start,
                           size_t next_cap, size_t* next_len, int* more) {
  Store* st = static_cast<Store*>(s);
  std::shared_lock<std::shared_mutex> lock(st->mu);
  uint64_t at = snap ? snap : st->ts;
  double now = wallclock();
  std::string lo(reinterpret_cast<const char*>(start), slen);
  std::string hi(reinterpret_cast<const char*>(end), elen);
  std::string tomb(reinterpret_cast<const char*>(tombstone), tomb_len);

  uint64_t row = 0, koff = 0, voff = 0;
  key_offs[0] = 0;
  val_offs[0] = 0;
  auto emit = [&](const char* k, size_t kl, const std::string& v,
                  uint64_t rev) -> bool {
    if (row >= max_rows || koff + kl > key_cap || voff + v.size() > val_cap)
      return false;
    memcpy(key_arena + koff, k, kl);
    koff += kl;
    key_offs[row + 1] = koff;
    memcpy(val_arena + voff, v.data(), v.size());
    voff += v.size();
    val_offs[row + 1] = voff;
    revs_out[row] = rev;
    ++row;
    return true;
  };
  std::string resume;
  uint64_t rows = mvcc_list_walk(st, lo, hi, at, now, read_rev, magic,
                                 magic_len, tomb, emit, &resume, more);
  if (resume.size() > next_cap) {
    *more = 2;  // resume cursor does not fit: caller must grow next_cap
    *next_len = resume.size();
    return rows;
  }
  memcpy(next_start, resume.data(), resume.size());
  *next_len = resume.size();
  return rows;
}

// One MVCC list page as READY protobuf wire bytes: the `repeated KeyValue
// kvs = 2` field of an etcd RangeResponse, each row by wire_put_row above
// (the one definition of the layout). The caller prepends the scalar
// fields (header/more/count) encoded by python-protobuf; field order is
// free in protobuf, so concatenation is a valid message. *out is malloc'd
// (kb_free it). Returns rows encoded.
uint64_t kb_mvcc_list_wire(void* s, const uint8_t* start, size_t slen,
                           const uint8_t* end, size_t elen, uint64_t snap,
                           uint64_t read_rev, const uint8_t* magic,
                           size_t magic_len, const uint8_t* tombstone,
                           size_t tomb_len, uint64_t max_rows,
                           uint64_t byte_cap, uint8_t** out, size_t* out_len,
                           uint8_t* next_start, size_t next_cap,
                           size_t* next_len, int* more) {
  Store* st = static_cast<Store*>(s);
  std::shared_lock<std::shared_mutex> lock(st->mu);
  uint64_t at = snap ? snap : st->ts;
  double now = wallclock();
  std::string lo(reinterpret_cast<const char*>(start), slen);
  std::string hi(reinterpret_cast<const char*>(end), elen);
  std::string tomb(reinterpret_cast<const char*>(tombstone), tomb_len);

  std::string blob;
  uint64_t row = 0;
  auto emit = [&](const char* k, size_t kl, const std::string& v,
                  uint64_t rev) -> bool {
    if (row >= max_rows || blob.size() >= byte_cap) return false;
    size_t at_byte = blob.size();
    blob.resize(at_byte + wire_row_size(kl, v.size(), rev));
    wire_put_row(reinterpret_cast<uint8_t*>(&blob[at_byte]), k, kl, v.data(),
                 v.size(), rev);
    ++row;
    return true;
  };
  std::string resume;
  uint64_t rows = mvcc_list_walk(st, lo, hi, at, now, read_rev, magic,
                                 magic_len, tomb, emit, &resume, more);
  uint8_t* buf = static_cast<uint8_t*>(malloc(blob.size() ? blob.size() : 1));
  memcpy(buf, blob.data(), blob.size());
  *out = buf;
  *out_len = blob.size();
  if (resume.size() > next_cap) {
    *more = 2;  // resume cursor does not fit: caller must grow next_cap
    *next_len = resume.size();
    return rows;
  }
  memcpy(next_start, resume.data(), resume.size());
  *next_len = resume.size();
  return rows;
}

// Rows of the device mirror's host arrays as the same READY wire bytes: a
// pure function over plain arrays (no Store*), so a ctypes caller runs it
// with the GIL released and concurrent listers gather in parallel. A reply
// is a sequence of RUNS, each the rows [0, n) of one source of arrays, 8
// words a run: keys (the decoded key matrix of the source's visible rows),
// the matrix's row stride, key_lens (int32), revs (uint64) — all three
// row-aligned — then val_arena, val_offsets (uint64) and rows (int64): row
// i takes its value from val_arena[val_offsets[rows[i]] ..
// val_offsets[rows[i] + 1]] (one partition's arena, by row index), and n.
// One call writes the whole reply, so the overlay's entries spliced
// between the mirror's runs cost no further round through the caller.
// Returns the bytes the runs need; they are written to out only when that
// fits out_cap — a short buffer is never written past, the caller reads
// the size and calls again.
size_t kb_wire_gather(const uint64_t* runs, size_t n_runs, uint8_t* out,
                      size_t out_cap) {
  struct Run {
    const uint8_t* keys;
    size_t key_stride;
    const int32_t* key_lens;
    const uint64_t* revs;
    const uint8_t* val_arena;
    const uint64_t* val_offsets;
    const int64_t* rows;
    size_t n;
  };
  auto run_at = [runs](size_t r) {
    const uint64_t* w = runs + 8 * r;
    return Run{reinterpret_cast<const uint8_t*>(w[0]),
               static_cast<size_t>(w[1]),
               reinterpret_cast<const int32_t*>(w[2]),
               reinterpret_cast<const uint64_t*>(w[3]),
               reinterpret_cast<const uint8_t*>(w[4]),
               reinterpret_cast<const uint64_t*>(w[5]),
               reinterpret_cast<const int64_t*>(w[6]),
               static_cast<size_t>(w[7])};
  };
  size_t need = 0;
  for (size_t r = 0; r < n_runs; ++r) {
    Run u = run_at(r);
    for (size_t i = 0; i < u.n; ++i) {
      size_t vl = u.val_offsets[u.rows[i] + 1] - u.val_offsets[u.rows[i]];
      need += wire_row_size(static_cast<size_t>(u.key_lens[i]), vl, u.revs[i]);
    }
  }
  if (need > out_cap) return need;
  uint8_t* p = out;
  for (size_t r = 0; r < n_runs; ++r) {
    Run u = run_at(r);
    for (size_t i = 0; i < u.n; ++i) {
      uint64_t vo = u.val_offsets[u.rows[i]];
      p = wire_put_row(p, u.keys + i * u.key_stride,
                       static_cast<size_t>(u.key_lens[i]), u.val_arena + vo,
                       u.val_offsets[u.rows[i] + 1] - vo, u.revs[i]);
    }
  }
  return need;
}

}  // extern "C"

namespace {

// The mirror's key dictionary as the wire read sees it (storage/tpu/
// encode.py, KeyEncoding.wire_table): bucket `code` strips `strip_lens[code]`
// leading bytes, kept in row `code` of the `strips` matrix.
struct WireDict {
  const int64_t* strip_lens;
  const uint8_t* strips;
  size_t stride, n_codes, suffix_width, raw_width;
};

// One partition's columns as the mirror holds them, and the rows of it that
// the device found visible.
struct WirePart {
  const uint32_t* keys;  // [N, chunks], each chunk a big-endian 4 bytes
  const int32_t* lens;   // key bytes (raw mirror) or suffix bytes (encoded)
  const uint64_t* revs;
  const uint8_t* val_arena;
  const uint64_t* val_offsets;
  size_t n_rows;  // rows that have a value: val_offsets holds n_rows + 1
  const int32_t* idx;
  size_t n_idx;
};

// n_bytes of a chunk row, rounded up to whole chunks: dst has that room.
inline void wire_chunk_bytes(const uint32_t* chunks, size_t n_bytes,
                             uint8_t* dst) {
  for (size_t j = 0; 4 * j < n_bytes; ++j) {
    uint32_t v = chunks[j];
    dst[4 * j] = static_cast<uint8_t>(v >> 24);
    dst[4 * j + 1] = static_cast<uint8_t>(v >> 16);
    dst[4 * j + 2] = static_cast<uint8_t>(v >> 8);
    dst[4 * j + 3] = static_cast<uint8_t>(v);
  }
}

// A stored row's user key into dst (room: raw_width + 4 * chunks): the twin
// of Mirror.decoded_keys — keys.chunks_to_u8 for a raw mirror (no
// dictionary), KeyEncoding.decode_rows for an encoded one, strip then
// suffix, zeros where decode_rows leaves its zeros — held to it by
// tests/test_wire_read.py. With dst null only the length. Returns the key's
// length, SIZE_MAX for a code the dictionary does not have.
inline size_t wire_key(const uint32_t* row, size_t chunks, int32_t len,
                       const WireDict* d, uint8_t* dst) {
  size_t n = len > 0 ? static_cast<size_t>(len) : 0;
  if (d == nullptr) {
    if (n > 4 * chunks) n = 4 * chunks;
    if (dst) wire_chunk_bytes(row, n, dst);
    return n;
  }
  size_t code = row[0];
  if (code >= d->n_codes || d->strip_lens[code] < 0) return SIZE_MAX;
  size_t s = static_cast<size_t>(d->strip_lens[code]);
  if (s > d->raw_width || s > d->stride) return SIZE_MAX;
  size_t kl = s + n < d->raw_width ? s + n : d->raw_width;
  if (dst == nullptr) return kl;
  if (s) memcpy(dst, d->strips + code * d->stride, s);
  size_t take = d->raw_width - s;
  if (take > d->suffix_width) take = d->suffix_width;
  if (take > 4 * (chunks - 1)) take = 4 * (chunks - 1);
  if (take > kl - s) take = kl - s;
  wire_chunk_bytes(row + 1, take, dst + s);
  if (s + take < kl) memset(dst + s + take, 0, kl - s - take);
  return kl;
}

inline int wire_key_cmp(const uint8_t* a, size_t al, const uint8_t* b,
                        size_t bl) {
  int c = memcmp(a, b, al < bl ? al : bl);
  return c ? c : (al < bl ? -1 : al > bl);
}

// The overlay's entries in key order: what the delta holds for the range,
// newer than anything in the mirror. A dead entry is a deletion.
struct WireOverlay {
  size_t n;
  const uint8_t* keys;
  const uint64_t* key_offs;
  const uint8_t* vals;
  const uint64_t* val_offs;
  const uint64_t* revs;
  const uint8_t* dead;
};

// The rows of one reply in key order: the partitions' visible rows and the
// overlay's entries walked once, side by side — an entry goes out in its
// place if it lives, and the mirror row of its key drops out. emit(key,
// key_len, value, value_len, rev) for each row until `limit` are out (0: no
// limit); *more says another row was there. With `bytes` false emit gets
// the mirror rows' key LENGTHS alone (a null key) once no overlay entry is
// left to compare with. False for a row index, a key code or a pair of
// value offsets that the arrays cannot hold.
template <typename Emit>
bool wire_walk(const WirePart* parts, size_t n_parts, size_t chunks,
               const WireDict* d, const WireOverlay& ov, uint64_t limit,
               bool bytes, uint8_t* scratch, Emit emit, uint64_t* rows,
               int* more) {
  uint64_t n = 0;
  size_t j = 0;
  *more = 0;
  auto put = [&](const uint8_t* k, size_t kl, const uint8_t* v, size_t vl,
                 uint64_t rev) {
    if (limit && n == limit) {
      *more = 1;
      return false;
    }
    emit(k, kl, v, vl, rev);
    ++n;
    return true;
  };
  auto put_entry = [&](size_t e) {
    if (ov.dead[e]) return true;
    return put(ov.keys + ov.key_offs[e], ov.key_offs[e + 1] - ov.key_offs[e],
               ov.vals + ov.val_offs[e], ov.val_offs[e + 1] - ov.val_offs[e],
               ov.revs[e]);
  };
  *rows = 0;
  for (size_t p = 0; p < n_parts; ++p) {
    const WirePart& u = parts[p];
    for (size_t i = 0; i < u.n_idx; ++i) {
      if (u.idx[i] < 0 || static_cast<size_t>(u.idx[i]) >= u.n_rows)
        return false;
      size_t r = static_cast<size_t>(u.idx[i]);
      uint64_t vo = u.val_offsets[r], ve = u.val_offsets[r + 1];
      if (ve < vo) return false;
      bool decode = bytes || j < ov.n;
      size_t kl = wire_key(u.keys + r * chunks, chunks, u.lens[r], d,
                           decode ? scratch : nullptr);
      if (kl == SIZE_MAX) return false;
      bool superseded = false;
      while (j < ov.n) {
        int c = wire_key_cmp(ov.keys + ov.key_offs[j],
                             ov.key_offs[j + 1] - ov.key_offs[j], scratch, kl);
        if (c > 0) break;
        if (!put_entry(j)) goto done;
        ++j;
        if (c == 0) {
          superseded = true;
          break;
        }
      }
      if (superseded) continue;
      if (!put(decode ? scratch : nullptr, kl, u.val_arena + vo, ve - vo,
               u.revs[r]))
        goto done;
    }
  }
  for (; j < ov.n; ++j)
    if (!put_entry(j)) break;
done:
  *rows = n;
  return true;
}

}  // namespace

extern "C" {

// The host half of one device-path wire read, whole: from the row indices
// the device handed back to the READY wire bytes, one call and no Python
// between — the key decode (wire_key), the overlay merge and the cut at
// `limit` (wire_walk), each row through wire_put_row. A pure function over
// plain arrays (no Store*), so a ctypes caller runs it with the GIL
// released: a lister gives the GIL up once a reply, the writers' handlers
// have it meanwhile, and concurrent listers run in parallel.
//
// parts: 8 words a partition that has visible rows, in key order — keys
// (uint32[N, key_chunks], as the mirror stores them), lens (int32[N]), revs
// (uint64[N]), val_arena, val_offsets (uint64[n_rows + 1]), n_rows, idx
// (int32: the visible rows, ascending), how many. dict: null for a raw
// mirror, else 6 words — strip_lens (int64[n_codes]), the strips matrix
// (uint8[n_codes, stride]), stride, n_codes, suffix_width, raw_width.
// The overlay: n_ov entries in key order, keys and values each one blob
// with n_ov + 1 offsets, revisions, and a flag a deletion (its value empty).
//
// Returns the bytes the reply needs, with *rows and *more; they are written
// to out only when that fits out_cap — a short buffer is never written
// past, the caller reads the size and calls again. SIZE_MAX: an index, a
// key code or a pair of offsets outside the arrays; nothing was written.
size_t kb_wire_read(const uint64_t* parts, size_t n_parts, size_t key_chunks,
                    const uint64_t* dict, size_t n_ov, const uint8_t* ov_keys,
                    const uint64_t* ov_key_offs, const uint8_t* ov_vals,
                    const uint64_t* ov_val_offs, const uint64_t* ov_revs,
                    const uint8_t* ov_dead, uint64_t limit, uint8_t* out,
                    size_t out_cap, uint64_t* rows, int* more) {
  std::vector<WirePart> ps(n_parts);
  for (size_t p = 0; p < n_parts; ++p) {
    const uint64_t* w = parts + 8 * p;
    ps[p] = WirePart{reinterpret_cast<const uint32_t*>(w[0]),
                     reinterpret_cast<const int32_t*>(w[1]),
                     reinterpret_cast<const uint64_t*>(w[2]),
                     reinterpret_cast<const uint8_t*>(w[3]),
                     reinterpret_cast<const uint64_t*>(w[4]),
                     static_cast<size_t>(w[5]),
                     reinterpret_cast<const int32_t*>(w[6]),
                     static_cast<size_t>(w[7])};
  }
  WireDict dict_s{};
  const WireDict* d = nullptr;
  if (dict != nullptr) {
    dict_s = WireDict{reinterpret_cast<const int64_t*>(dict[0]),
                      reinterpret_cast<const uint8_t*>(dict[1]),
                      static_cast<size_t>(dict[2]),
                      static_cast<size_t>(dict[3]),
                      static_cast<size_t>(dict[4]),
                      static_cast<size_t>(dict[5])};
    d = &dict_s;
    if (key_chunks == 0) return SIZE_MAX;  // no chunk for the code
  }
  WireOverlay ov{n_ov,        ov_keys, ov_key_offs, ov_vals,
                 ov_val_offs, ov_revs, ov_dead};
  std::vector<uint8_t> scratch((d ? d->raw_width : 0) + 4 * key_chunks + 4);
  size_t need = 0;
  if (!wire_walk(
          ps.data(), n_parts, key_chunks, d, ov, limit, false, scratch.data(),
          [&need](const uint8_t*, size_t kl, const uint8_t*, size_t vl,
                  uint64_t rev) { need += wire_row_size(kl, vl, rev); },
          rows, more))
    return SIZE_MAX;
  if (need > out_cap) return need;
  uint8_t* at = out;
  wire_walk(
      ps.data(), n_parts, key_chunks, d, ov, limit, true, scratch.data(),
      [&at](const uint8_t* k, size_t kl, const uint8_t* v, size_t vl,
            uint64_t rev) { at = wire_put_row(at, k, kl, v, vl, rev); },
      rows, more);
  return need;
}

// Paged columnar export for the kbstored EXPORT op (the bulk path that lets
// a remote TPU mirror rebuild without per-row Python; reference analogue:
// the TiKV adapter feeding the scanner's partition map, tikv.go:38-153).
// One pass from `start`, stopping at max_rows exported rows or arena_cap
// value bytes; builds the wire page directly:
//   u32 n | u8 more | u32 next_len | next_start |
//   keys u8[n*key_width] | lens i32[n] | revs u64[n] | tomb u8[n] |
//   u64 arena_len | arena | u64 offsets[n+1]
// `more` set => resume with start = next_start (inclusive). Returns 0 ok /
// 1 key-wider-than-key_width. *out is malloc'd; kb_free it.
int kb_mvcc_export_wire(void* s, const uint8_t* start, size_t slen,
                        const uint8_t* end, size_t elen, uint64_t snap,
                        const uint8_t* magic, size_t magic_len,
                        const uint8_t* tombstone, size_t tomb_len,
                        uint64_t key_width, uint64_t max_rows,
                        uint64_t arena_cap, uint8_t** out, size_t* out_len) {
  Store* st = static_cast<Store*>(s);
  std::shared_lock<std::shared_mutex> lock(st->mu);
  uint64_t at = snap ? snap : st->ts;
  double now = wallclock();
  std::string lo(reinterpret_cast<const char*>(start), slen);
  std::string hi(reinterpret_cast<const char*>(end), elen);
  std::string tomb(reinterpret_cast<const char*>(tombstone), tomb_len);

  std::vector<uint8_t> keys;
  std::vector<int32_t> lens;
  std::vector<uint64_t> revs;
  std::vector<uint8_t> tombs;
  std::string arena;
  std::vector<uint64_t> offsets{0};
  std::string next_start;
  bool more = false;

  auto b = st->data.lower_bound(lo);
  auto e = hi.empty() ? st->data.end() : st->data.lower_bound(hi);
  for (auto cur = b; cur != e; ++cur) {
    size_t klen;
    uint64_t rev;
    if (!parse_internal(cur->first, magic, magic_len, &klen, &rev)) continue;
    if (rev == 0) continue;
    const std::string* v = st->live(cur->first, at, now);
    if (v == nullptr) continue;
    if (klen > key_width) return 1;
    if (revs.size() >= max_rows || arena.size() >= arena_cap) {
      more = true;
      next_start = cur->first;  // resume inclusive from this raw key
      break;
    }
    size_t row = revs.size();
    keys.resize((row + 1) * key_width, 0);
    memcpy(keys.data() + row * key_width, cur->first.data() + magic_len, klen);
    lens.push_back(static_cast<int32_t>(klen));
    revs.push_back(rev);
    tombs.push_back(*v == tomb ? 1 : 0);
    arena.append(*v);
    offsets.push_back(arena.size());
  }

  uint32_t n = static_cast<uint32_t>(revs.size());
  size_t total = 4 + 1 + 4 + next_start.size() + keys.size() + n * 4 + n * 8 +
                 n + 8 + arena.size() + (n + 1) * 8;
  uint8_t* buf = static_cast<uint8_t*>(malloc(total));
  if (buf == nullptr) return 1;
  uint8_t* p = buf;
  auto put = [&p](const void* src, size_t len) {
    memcpy(p, src, len);
    p += len;
  };
  uint32_t next_len = static_cast<uint32_t>(next_start.size());
  uint8_t more8 = more ? 1 : 0;
  uint64_t alen = arena.size();
  put(&n, 4);
  put(&more8, 1);
  put(&next_len, 4);
  put(next_start.data(), next_start.size());
  put(keys.data(), keys.size());
  put(lens.data(), n * 4);
  put(revs.data(), n * 8);
  put(tombs.data(), n);
  put(&alen, 8);
  put(arena.data(), arena.size());
  put(offsets.data(), (n + 1) * 8);
  *out = buf;
  *out_len = total;
  return 0;
}

}  // extern "C"
