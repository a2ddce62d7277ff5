// libcfskv — persistent ordered KV store, the rebuild's RocksDB stand-in.
//
// Reference counterpart: blobstore/common/kvstore/db.go:28,115-181 (cgo →
// C++ RocksDB) and raftstore/raftstore_db (RocksDB-backed WAL/store helpers).
// The reference links the real RocksDB; this rebuild keeps the same role —
// a native, crash-safe, ordered KV engine behind a C ABI — with a design
// sized to how CubeFS actually uses it: point get/put/delete, atomic write
// batches, prefix scans over ordered keys, checkpoints for raft snapshots.
//
// Engine: single-writer log-structured store (bitcask lineage). All
// mutations append CRC-framed records to numbered .log files; an in-memory
// ordered index (std::map) maps keys to live values. Recovery replays the
// logs in order, truncating a torn tail. Compaction rewrites live data into
// a fresh log and deletes the old generation. Batches are one framed record,
// so they apply atomically across a crash.
//
// Record framing (little-endian):
//   [u32 crc over everything after it][u8 type][u32 klen][u32 vlen]
//   [key bytes][val bytes]
// type: 1=put 2=del 3=batch (payload = concatenated sub-records of
// [u8 type][u32 klen][u32 vlen][key][val]).

#include <algorithm>
#include <cerrno>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <dirent.h>
#include <fcntl.h>
#include <map>
#include <mutex>
#include <string>
#include <sys/file.h>
#include <sys/stat.h>
#include <sys/types.h>
#include <sys/uio.h>
#include <unistd.h>
#include <vector>

namespace {

constexpr uint8_t kPut = 1;
constexpr uint8_t kDel = 2;
constexpr uint8_t kBatch = 3;
constexpr uint64_t kCompactMinDead = 4u << 20;  // rewrite when ≥4MiB is dead

// CRC32 (IEEE, same polynomial as zlib.crc32 — the Python fallback engines
// write byte-identical files). Slicing-by-16: sixteen independent table
// look-ups per two 64-bit words instead of a dependent chain a byte, so a
// blobnode shard frames at GB/s. The kvstore's log records and the chunk
// framing below share it.
uint32_t crc_table[16][256];
struct CrcInit {
  CrcInit() {
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int k = 0; k < 8; k++) c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      crc_table[0][i] = c;
    }
    for (uint32_t i = 0; i < 256; i++)
      for (int t = 1; t < 16; t++)
        crc_table[t][i] =
            crc_table[0][crc_table[t - 1][i] & 0xFF] ^ (crc_table[t - 1][i] >> 8);
  }
} crc_init;

uint32_t crc32(const uint8_t* p, size_t n, uint32_t c = 0) {
  c = ~c;
#if __BYTE_ORDER__ == __ORDER_LITTLE_ENDIAN__
  for (; n >= 16; p += 16, n -= 16) {
    uint64_t a, b;
    memcpy(&a, p, 8);
    memcpy(&b, p + 8, 8);
    a ^= c;
    c = crc_table[15][a & 0xFF] ^ crc_table[14][(a >> 8) & 0xFF] ^
        crc_table[13][(a >> 16) & 0xFF] ^ crc_table[12][(a >> 24) & 0xFF] ^
        crc_table[11][(a >> 32) & 0xFF] ^ crc_table[10][(a >> 40) & 0xFF] ^
        crc_table[9][(a >> 48) & 0xFF] ^ crc_table[8][a >> 56] ^
        crc_table[7][b & 0xFF] ^ crc_table[6][(b >> 8) & 0xFF] ^
        crc_table[5][(b >> 16) & 0xFF] ^ crc_table[4][(b >> 24) & 0xFF] ^
        crc_table[3][(b >> 32) & 0xFF] ^ crc_table[2][(b >> 40) & 0xFF] ^
        crc_table[1][(b >> 48) & 0xFF] ^ crc_table[0][b >> 56];
  }
#endif
  for (size_t i = 0; i < n; i++) c = crc_table[0][(c ^ p[i]) & 0xFF] ^ (c >> 8);
  return ~c;
}

void put_u32(std::string& s, uint32_t v) {
  s.push_back(char(v & 0xFF));
  s.push_back(char((v >> 8) & 0xFF));
  s.push_back(char((v >> 16) & 0xFF));
  s.push_back(char((v >> 24) & 0xFF));
}

uint32_t get_u32(const uint8_t* p) {
  return uint32_t(p[0]) | uint32_t(p[1]) << 8 | uint32_t(p[2]) << 16 |
         uint32_t(p[3]) << 24;
}

std::string log_name(uint64_t id) {
  char buf[32];
  snprintf(buf, sizeof buf, "%08llu.log", (unsigned long long)id);
  return buf;
}

struct DB {
  std::string dir;
  std::map<std::string, std::string> index;  // live key -> value
  FILE* active = nullptr;
  int lock_fd = -1;  // flock'd LOCK file: one live handle per dir (à la RocksDB)
  uint64_t active_id = 0;
  uint64_t live_bytes = 0;   // bytes of live records
  uint64_t total_bytes = 0;  // bytes appended across all logs
  std::mutex mu;
  std::string err;

  ~DB() {
    if (active) fclose(active);
    if (lock_fd >= 0) close(lock_fd);  // releases the flock
  }

  bool fail(const std::string& msg) {
    err = msg + " (errno " + std::to_string(errno) + ")";
    return false;
  }

  // -- record building -------------------------------------------------------

  static std::string sub_record(uint8_t type, const std::string& k,
                                const std::string& v) {
    std::string body;
    body.push_back(char(type));
    put_u32(body, uint32_t(k.size()));
    put_u32(body, uint32_t(v.size()));
    body += k;
    body += v;
    return body;
  }

  static std::string frame(const std::string& body) {
    std::string out;
    put_u32(out, crc32((const uint8_t*)body.data(), body.size()));
    out += body;
    return out;
  }

  bool append(const std::string& framed) {
    if (fwrite(framed.data(), 1, framed.size(), active) != framed.size())
      return fail("append");
    if (fflush(active) != 0) return fail("flush");
    total_bytes += framed.size();
    return true;
  }

  // -- apply to index --------------------------------------------------------

  void apply(uint8_t type, const std::string& k, const std::string& v) {
    if (type == kPut) {
      auto it = index.find(k);
      if (it != index.end()) live_bytes -= it->second.size() + k.size();
      index[k] = v;
      live_bytes += k.size() + v.size();
    } else if (type == kDel) {
      auto it = index.find(k);
      if (it != index.end()) {
        live_bytes -= it->second.size() + k.size();
        index.erase(it);
      }
    }
  }

  bool apply_body(const uint8_t* p, size_t n) {
    if (n < 9) return false;
    uint8_t type = p[0];
    if (type == kBatch) {
      // klen reused as sub-op count, vlen = payload length
      uint32_t count = get_u32(p + 1), plen = get_u32(p + 5);
      if (9 + plen != n) return false;
      const uint8_t* q = p + 9;
      size_t rem = plen;
      for (uint32_t i = 0; i < count; i++) {
        if (rem < 9) return false;
        uint8_t t = q[0];
        uint32_t kl = get_u32(q + 1), vl = get_u32(q + 5);
        if (rem < 9 + (size_t)kl + vl) return false;
        apply(t, std::string((const char*)q + 9, kl),
              std::string((const char*)q + 9 + kl, vl));
        q += 9 + kl + vl;
        rem -= 9 + (size_t)kl + vl;
      }
      return rem == 0;
    }
    uint32_t kl = get_u32(p + 1), vl = get_u32(p + 5);
    if (9 + (size_t)kl + vl != n) return false;
    apply(type, std::string((const char*)p + 9, kl),
          std::string((const char*)p + 9 + kl, vl));
    return true;
  }

  // -- recovery --------------------------------------------------------------

  bool replay_file(const std::string& path, bool is_last) {
    FILE* f = fopen(path.c_str(), "rb");
    if (!f) return fail("open " + path);
    std::string data;
    char buf[1 << 16];
    size_t n;
    while ((n = fread(buf, 1, sizeof buf, f)) > 0) data.append(buf, n);
    fclose(f);
    size_t off = 0;
    const uint8_t* p = (const uint8_t*)data.data();
    while (off + 13 <= data.size()) {
      uint32_t crc = get_u32(p + off);
      uint8_t type = p[off + 4];
      uint32_t a = get_u32(p + off + 5), b = get_u32(p + off + 9);
      size_t body_len =
          type == kBatch ? 9 + (size_t)b : 9 + (size_t)a + b;
      if (off + 4 + body_len > data.size()) break;  // torn tail
      if (crc32(p + off + 4, body_len) != crc) break;  // corrupt tail
      if (!apply_body(p + off + 4, body_len)) break;
      off += 4 + body_len;
    }
    total_bytes += off;
    if (off != data.size()) {
      // torn write: keep the clean prefix. Only legitimate on the newest
      // log; anywhere else it means lost updates, so surface an error.
      if (!is_last) return fail("corrupt log " + path);
      if (truncate(path.c_str(), (off_t)off) != 0)
        return fail("truncate " + path);
    }
    return true;
  }

  bool open_dir(const std::string& d) {
    dir = d;
    mkdir(dir.c_str(), 0755);
    // a second live handle on the same dir would lose appends when the first
    // compacts away its log generation; refuse loudly instead
    lock_fd = open((dir + "/LOCK").c_str(), O_CREAT | O_RDWR, 0644);
    if (lock_fd < 0) return fail("open LOCK");
    if (flock(lock_fd, LOCK_EX | LOCK_NB) != 0)
      return fail("store already open (LOCK held)");
    std::vector<uint64_t> ids;
    DIR* dp = opendir(dir.c_str());
    if (!dp) return fail("opendir " + dir);
    while (dirent* e = readdir(dp)) {
      std::string name = e->d_name;
      if (name.size() == 12 && name.substr(8) == ".log")
        ids.push_back(strtoull(name.c_str(), nullptr, 10));
    }
    closedir(dp);
    std::sort(ids.begin(), ids.end());
    for (size_t i = 0; i < ids.size(); i++)
      if (!replay_file(dir + "/" + log_name(ids[i]), i + 1 == ids.size()))
        return false;
    active_id = ids.empty() ? 1 : ids.back();
    active = fopen((dir + "/" + log_name(active_id)).c_str(), "ab");
    if (!active) return fail("open active log");
    return true;
  }

  // -- compaction ------------------------------------------------------------

  bool compact() {
    uint64_t next = active_id + 1;
    std::string tmp = dir + "/" + log_name(next) + ".tmp";
    FILE* out = fopen(tmp.c_str(), "wb");
    if (!out) return fail("compact open");
    uint64_t written = 0;
    for (auto& [k, v] : index) {
      std::string rec = frame(sub_record(kPut, k, v));
      if (fwrite(rec.data(), 1, rec.size(), out) != rec.size()) {
        fclose(out);
        return fail("compact write");
      }
      written += rec.size();
    }
    if (fflush(out) != 0 || fsync(fileno(out)) != 0) {
      fclose(out);
      return fail("compact sync");
    }
    fclose(out);
    if (rename(tmp.c_str(), (dir + "/" + log_name(next)).c_str()) != 0)
      return fail("compact rename");
    // older generations are now redundant
    fclose(active);
    for (uint64_t id = 1; id <= active_id; id++)
      remove((dir + "/" + log_name(id)).c_str());
    active_id = next;
    active = fopen((dir + "/" + log_name(active_id)).c_str(), "ab");
    if (!active) return fail("compact reopen");
    total_bytes = written;
    return true;
  }

  bool maybe_compact() {
    if (total_bytes > live_bytes + index.size() * 13 + kCompactMinDead)
      return compact();
    return true;
  }

  // -- checkpoint (raft snapshot feed; RocksDB Checkpoint analog) ------------

  bool checkpoint(const std::string& out_dir) {
    mkdir(out_dir.c_str(), 0755);
    // a compacted copy IS the checkpoint: one log holding exactly the live set
    std::string tmp = out_dir + "/" + log_name(1) + ".tmp";
    FILE* out = fopen(tmp.c_str(), "wb");
    if (!out) return fail("checkpoint open");
    for (auto& [k, v] : index) {
      std::string rec = frame(sub_record(kPut, k, v));
      if (fwrite(rec.data(), 1, rec.size(), out) != rec.size()) {
        fclose(out);
        return fail("checkpoint write");
      }
    }
    if (fflush(out) != 0 || fsync(fileno(out)) != 0) {
      fclose(out);
      return fail("checkpoint sync");
    }
    fclose(out);
    if (rename(tmp.c_str(), (out_dir + "/" + log_name(1)).c_str()) != 0)
      return fail("checkpoint rename");
    return true;
  }
};

// -- vectored positional I/O for the shard calls at the end of the file ------

constexpr long kIovBlocks = 256;  // 2 x 256 + 1 entries: under IOV_MAX (1024)

// pwritev/preadv of all of iov[0..cnt) at `pos`, resuming a short transfer
// where it stopped. Returns the bytes moved (less than asked only at end of
// file, on a read) or -errno. Consumes iov.
template <typename Fn>
long xfer_all(Fn fn, int fd, struct iovec* iov, int cnt, long pos) {
  long done = 0;
  while (cnt > 0) {
    ssize_t r = fn(fd, iov, cnt, pos + done);
    if (r < 0) {
      if (errno == EINTR) continue;
      return -errno;
    }
    if (r == 0) break;  // end of file (read); a write of nothing cannot go on
    done += r;
    while (cnt > 0 && size_t(r) >= iov->iov_len) {
      r -= iov->iov_len;
      iov++, cnt--;
    }
    if (cnt > 0) {
      iov->iov_base = (uint8_t*)iov->iov_base + r;
      iov->iov_len -= r;
    }
  }
  return done;
}

}  // namespace

extern "C" {

void* cfskv_open(const char* dir, char* errbuf, int errlen) {
  DB* db = new DB();
  if (!db->open_dir(dir)) {
    if (errbuf && errlen > 0) {
      strncpy(errbuf, db->err.c_str(), errlen - 1);
      errbuf[errlen - 1] = 0;
    }
    delete db;
    return nullptr;
  }
  return db;
}

void cfskv_close(void* h) { delete (DB*)h; }

const char* cfskv_errmsg(void* h) { return ((DB*)h)->err.c_str(); }

int cfskv_put(void* h, const char* k, int klen, const char* v, int vlen) {
  DB* db = (DB*)h;
  std::lock_guard<std::mutex> g(db->mu);
  std::string key(k, klen), val(v, vlen);
  if (!db->append(DB::frame(DB::sub_record(kPut, key, val)))) return -1;
  db->apply(kPut, key, val);
  return db->maybe_compact() ? 0 : -1;
}

int cfskv_del(void* h, const char* k, int klen) {
  DB* db = (DB*)h;
  std::lock_guard<std::mutex> g(db->mu);
  std::string key(k, klen);
  if (!db->append(DB::frame(DB::sub_record(kDel, key, "")))) return -1;
  db->apply(kDel, key, "");
  return db->maybe_compact() ? 0 : -1;
}

// 0 = found (out/outlen set, free with cfskv_free), 1 = not found
int cfskv_get(void* h, const char* k, int klen, char** out, int* outlen) {
  DB* db = (DB*)h;
  std::lock_guard<std::mutex> g(db->mu);
  auto it = db->index.find(std::string(k, klen));
  if (it == db->index.end()) return 1;
  *out = (char*)malloc(it->second.size());
  memcpy(*out, it->second.data(), it->second.size());
  *outlen = (int)it->second.size();
  return 0;
}

void cfskv_free(char* p) { free(p); }

// ops buffer: concatenated [u8 type][u32 klen][u32 vlen][key][val]; applied
// as ONE crash-atomic record.
int cfskv_batch(void* h, const char* ops, int opslen, int count) {
  DB* db = (DB*)h;
  std::lock_guard<std::mutex> g(db->mu);
  std::string body;
  body.push_back(char(kBatch));
  put_u32(body, uint32_t(count));
  put_u32(body, uint32_t(opslen));
  body.append(ops, opslen);
  if (!db->append(DB::frame(body))) return -1;
  if (!db->apply_body((const uint8_t*)body.data(), body.size())) {
    db->err = "malformed batch";
    return -1;
  }
  return db->maybe_compact() ? 0 : -1;
}

// Ordered scan of up to `limit` pairs with key >= start and key.startswith
// (prefix). Output: concatenated [u32 klen][u32 vlen][key][val]; free with
// cfskv_free. Returns pair count, -1 on error.
int cfskv_scan(void* h, const char* prefix, int plen, const char* start,
               int slen, int limit, char** out, int* outlen) {
  DB* db = (DB*)h;
  std::lock_guard<std::mutex> g(db->mu);
  std::string pre(prefix, plen), from(start, slen);
  if (from < pre) from = pre;
  std::string buf;
  int n = 0;
  for (auto it = db->index.lower_bound(from); it != db->index.end(); ++it) {
    if (it->first.compare(0, pre.size(), pre) != 0) break;
    put_u32(buf, uint32_t(it->first.size()));
    put_u32(buf, uint32_t(it->second.size()));
    buf += it->first;
    buf += it->second;
    if (++n == limit) break;
  }
  *out = (char*)malloc(buf.size() ? buf.size() : 1);
  memcpy(*out, buf.data(), buf.size());
  *outlen = (int)buf.size();
  return n;
}

long cfskv_count(void* h) {
  DB* db = (DB*)h;
  std::lock_guard<std::mutex> g(db->mu);
  return (long)db->index.size();
}

int cfskv_compact(void* h) {
  DB* db = (DB*)h;
  std::lock_guard<std::mutex> g(db->mu);
  return db->compact() ? 0 : -1;
}

int cfskv_checkpoint(void* h, const char* dir) {
  DB* db = (DB*)h;
  std::lock_guard<std::mutex> g(db->mu);
  return db->checkpoint(dir) ? 0 : -1;
}

// -- crc32block shard I/O (blobnode chunk files; utils/crc32block.py) --------
//
// A shard in a chunk datafile is [block][crc32(block)]... in blocks of
// `block` bytes, the last one short. Each call below frames (or verifies)
// every block AND does the file I/O, positional on the chunk's descriptor,
// with no Python anywhere between: under ctypes that is one release of the
// interpreter lock a shard, whatever its size, and no framed copy of the
// shard exists outside the page cache. Durability is what `write` + `flush`
// of a buffered file gave: the bytes are in the OS when the call returns,
// nothing here syncs them.

// head, then payload framed, written at `pos` of `fd` straight from the
// caller's buffers: one pwritev of head + (block, crc) pairs for every 16 MiB.
// Returns the bytes written (head_len + n + 4 a block) or -errno.
long cfs_shard_pwrite(int fd, long pos, const uint8_t* head, long head_len,
                      const uint8_t* payload, long n, long block) {
  struct iovec iov[2 * kIovBlocks + 1];
  uint8_t crcs[kIovBlocks][4];
  long total = 0, off = 0, want = head_len;
  int cnt = 0;
  if (head_len > 0) iov[cnt++] = {(void*)head, size_t(head_len)};
  do {
    for (long b = 0; b < kIovBlocks && off < n; b++, off += block) {
      long len = std::min(block, n - off);
      uint32_t c = crc32(payload + off, len);
      for (int i = 0; i < 4; i++) crcs[b][i] = uint8_t(c >> (8 * i));
      iov[cnt++] = {(void*)(payload + off), size_t(len)};
      iov[cnt++] = {crcs[b], 4};
      want += len + 4;
    }
    long got = xfer_all(pwritev, fd, iov, cnt, pos + total);
    if (got < 0) return got;
    if (got < want) return -EIO;  // pwritev moved nothing and set no errno
    total += got;
    cnt = 0, want = 0;
  } while (off < n);
  return total;
}

// Reads the `framed_len` framed bytes at `pos` of `fd` with one preadv for
// every 16 MiB, each block scattered to its place in `out` and its crc aside,
// and verifies every block where it lies. `*bad` is -1 when all pass, else
// the framed offset of the first block that does not (a tail too short to
// hold a block and its crc is such a block); `out` is then not to be used.
// Returns `framed_len`, fewer bytes where the file ends early, or -errno.
long cfs_shard_pread(int fd, long pos, long framed_len, long block,
                     uint8_t* out, long* bad) {
  struct iovec iov[2 * kIovBlocks];
  uint8_t crcs[kIovBlocks][4];
  *bad = -1;
  long end = framed_len;  // where the whole blocks end
  if (long tail = framed_len % (block + 4); tail > 0 && tail <= 4)
    *bad = end = framed_len - tail;
  for (long first = 0; first < end;) {
    int cnt = 0;
    long off = first, want = 0;
    uint8_t* dst = out;
    for (long b = 0; b < kIovBlocks && off < end; b++, off += block + 4) {
      long len = std::min(block + 4, end - off) - 4;
      iov[cnt++] = {dst, size_t(len)};
      iov[cnt++] = {crcs[b], 4};
      dst += len;
      want += len + 4;
    }
    long got = xfer_all(preadv, fd, iov, cnt, pos + first);
    if (got < 0) return got;
    if (got < want) return first + got;
    for (long b = 0; first < off; b++, first += block + 4) {
      long len = std::min(block + 4, end - first) - 4;
      if (crc32(out, len) != get_u32(crcs[b])) {
        *bad = first;
        return framed_len;
      }
      out += len;
    }
  }
  return framed_len;
}

}  // extern "C"
