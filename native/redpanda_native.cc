// redpanda_tpu native runtime helpers.
//
// TPU-native equivalent of the reference's native byte-plane: CRC32C
// (hardware-accelerated, mirroring its use of google/crc32c), xxhash-free
// framing helpers, and the hot host-side loop that packs variable-length
// records into fixed-shape [P, B, R] device staging buffers (and unpacks
// them back), which feeds the XLA data plane through the bridge.
//
// Exposed as a plain C ABI consumed via ctypes (no pybind11 in this image).

#include <cstdint>
#include <cstring>
#include <cstddef>
#include <cmath>
#include <cstdlib>
#include <atomic>
#include <mutex>
#include <thread>
#include <vector>
#include <dlfcn.h>
#include <functional>

#if defined(__x86_64__)
// x86intrin.h + per-function target attributes instead of a global -msse4.2:
// the .so must never carry SSE4.2 instructions outside runtime-dispatched
// functions, or a prebuilt binary SIGILLs on pre-Nehalem hosts. SSE2 is
// part of the x86_64 ABI baseline and is safe to use unguarded.
#include <emmintrin.h>
#include <x86intrin.h>
#define HAVE_X86_64 1
#endif

extern "C" {

// ---------------------------------------------------------------- crc32c
static uint32_t crc_table[8][256];
static bool crc_table_init_done = false;

static void crc_table_init() {
  if (crc_table_init_done) return;
  const uint32_t poly = 0x82F63B78u;
  for (uint32_t i = 0; i < 256; i++) {
    uint32_t c = i;
    for (int k = 0; k < 8; k++) c = (c >> 1) ^ ((c & 1) ? poly : 0);
    crc_table[0][i] = c;
  }
  for (int k = 1; k < 8; k++)
    for (uint32_t i = 0; i < 256; i++)
      crc_table[k][i] = crc_table[0][crc_table[k - 1][i] & 0xFF] ^
                        (crc_table[k - 1][i] >> 8);
  crc_table_init_done = true;
}

static uint32_t crc32c_sw(uint32_t crc, const uint8_t* p, size_t n) {
  crc_table_init();
  while (n >= 8) {
    crc ^= (uint32_t)p[0] | ((uint32_t)p[1] << 8) | ((uint32_t)p[2] << 16) |
           ((uint32_t)p[3] << 24);
    crc = crc_table[7][crc & 0xFF] ^ crc_table[6][(crc >> 8) & 0xFF] ^
          crc_table[5][(crc >> 16) & 0xFF] ^ crc_table[4][(crc >> 24) & 0xFF] ^
          crc_table[3][p[4]] ^ crc_table[2][p[5]] ^ crc_table[1][p[6]] ^
          crc_table[0][p[7]];
    p += 8;
    n -= 8;
  }
  while (n--) crc = crc_table[0][(crc ^ *p++) & 0xFF] ^ (crc >> 8);
  return crc;
}

#if HAVE_X86_64
__attribute__((target("sse4.2")))
static uint32_t crc32c_hw(uint32_t crc, const uint8_t* data, size_t len) {
  const uint8_t* p = data;
  size_t n = len;
  uint64_t c = crc;
  while (n && ((uintptr_t)p & 7)) { c = _mm_crc32_u8((uint32_t)c, *p++); n--; }
  while (n >= 8) {
    c = _mm_crc32_u64(c, *(const uint64_t*)p);
    p += 8;
    n -= 8;
  }
  while (n--) c = _mm_crc32_u8((uint32_t)c, *p++);
  return (uint32_t)c;
}
#endif

// crc is internal state (pre-inverted). Returns new internal state.
// Runtime feature dispatch: the SSE4.2 CRC32 instructions live only inside
// crc32c_hw (target attribute), picked once per process when the CPU
// actually has them — the same .so runs on any x86_64 (and any other arch
// via the table path). The pointer write is idempotent, so the unlocked
// first-call race is benign.
uint32_t rp_crc32c_update(uint32_t crc, const uint8_t* data, size_t len) {
#if HAVE_X86_64
  static uint32_t (*impl)(uint32_t, const uint8_t*, size_t) = nullptr;
  uint32_t (*fn)(uint32_t, const uint8_t*, size_t) = impl;
  if (!fn) {
    fn = __builtin_cpu_supports("sse4.2") ? crc32c_hw : crc32c_sw;
    impl = fn;
  }
  return fn(crc, data, len);
#else
  return crc32c_sw(crc, data, len);
#endif
}

// Final-value convenience: init 0xFFFFFFFF, xorout 0xFFFFFFFF.
uint32_t rp_crc32c(const uint8_t* data, size_t len) {
  return rp_crc32c_update(0xFFFFFFFFu, data, len) ^ 0xFFFFFFFFu;
}

// CRC N padded rows in one call: data is [n_rows, row_stride] row-major,
// lengths[i] gives the valid prefix of row i; out[i] = final CRC value.
void rp_crc32c_many(const uint8_t* data, size_t row_stride, size_t n_rows,
                    const int32_t* lengths, uint32_t* out) {
  for (size_t i = 0; i < n_rows; i++) {
    const uint8_t* row = data + i * row_stride;
    size_t len = lengths[i] < 0 ? 0 : (size_t)lengths[i];
    if (len > row_stride) len = row_stride;
    out[i] = rp_crc32c_update(0xFFFFFFFFu, row, len) ^ 0xFFFFFFFFu;
  }
}

// ---------------------------------------------------------------- packing
// Scatter n variable-length records (concatenated in `src` at `offsets`,
// sizes `sizes`) into a zero-padded [n, row_stride] staging buffer.
// Returns number of records whose size exceeded row_stride (truncated).
int32_t rp_pack_rows(const uint8_t* src, const int64_t* offsets,
                     const int32_t* sizes, size_t n, uint8_t* dst,
                     size_t row_stride) {
  int32_t truncated = 0;
  for (size_t i = 0; i < n; i++) {
    size_t sz = sizes[i] < 0 ? 0 : (size_t)sizes[i];
    if (sz > row_stride) {
      sz = row_stride;
      truncated++;
    }
    uint8_t* row = dst + i * row_stride;
    std::memcpy(row, src + offsets[i], sz);
    if (sz < row_stride) std::memset(row + sz, 0, row_stride - sz);
  }
  return truncated;
}

// The pointer-table twin of rp_pack_rows, and the payload staging lane's
// whole pack stage in one crossing: batch r's records take their
// (offset, len) RELATIVE to their own source buffer srcs[r] (one retained
// decompressed payload buffer a batch: PtrExploded) and own rows
// [starts[r], ends[r]) of the table. Row j of the staging matrix dst
// [n_pad, stride], stride = row_stride (the value part) + 8 meta bytes, is
// the table's row rows[j] (row numbers, ascending: one part of a launch
// staged by width class fills its matrix from the rows of its class), or
// row j itself where rows is NULL (the whole table, k its row count): the
// value and its zeroed tail as rp_pack_rows stages them, then the LE32
// length (0 for a null value and for one wider than THIS matrix's
// row_stride: staged, never transformed) and four zero bytes; rows
// k..n_pad are cleared. dst may hold anything on entry: a reused matrix
// comes out byte for byte like a fresh one. Everything is checked BEFORE
// anything is written: returns -1 on a span outside its buffer
// (src_lens[r]), on a row outside the table or on rows that do not ascend,
// else 0.
int64_t rp_pack_rows_ptrs(const uint8_t* const* srcs, const int64_t* src_lens,
                          const int64_t* offsets, const int32_t* lens,
                          const int64_t* starts, const int64_t* ends,
                          int64_t n_batches, const int64_t* rows, int64_t k,
                          uint8_t* dst, int64_t n_pad, size_t row_stride) {
  const size_t stride = row_stride + 8;
  int64_t r = 0, prev = -1;
  for (int64_t j = 0; j < k; j++) {
    int64_t i = rows ? rows[j] : j;
    if (i <= prev) return -1;
    prev = i;
    while (r < n_batches && i >= ends[r]) r++;
    if (r == n_batches || i < starts[r]) return -1;
    int64_t vlen = lens[i] < 0 ? 0 : lens[i];
    if (offsets[i] < 0 || offsets[i] + vlen > src_lens[r]) return -1;
  }
  r = 0;
  for (int64_t j = 0; j < k; j++) {
    int64_t i = rows ? rows[j] : j;
    while (i >= ends[r]) r++;
    size_t sz = lens[i] < 0 ? 0 : (size_t)lens[i];
    uint32_t len = sz > row_stride ? 0u : (uint32_t)sz;
    if (sz > row_stride) sz = row_stride;
    uint8_t* row = dst + (size_t)j * stride;
    std::memcpy(row, srcs[r] + offsets[i], sz);
    std::memset(row + sz, 0, stride - sz);
    row[row_stride] = (uint8_t)len;
    row[row_stride + 1] = (uint8_t)(len >> 8);
    row[row_stride + 2] = (uint8_t)(len >> 16);
    row[row_stride + 3] = (uint8_t)(len >> 24);
  }
  if (n_pad > k)
    std::memset(dst + (size_t)k * stride, 0, (size_t)(n_pad - k) * stride);
  return 0;
}

// Gather rows back out into a contiguous buffer; returns total bytes.
int64_t rp_unpack_rows(const uint8_t* src, size_t row_stride,
                       const int32_t* sizes, size_t n, uint8_t* dst) {
  int64_t total = 0;
  for (size_t i = 0; i < n; i++) {
    size_t sz = sizes[i] < 0 ? 0 : (size_t)sizes[i];
    if (sz > row_stride) sz = row_stride;
    std::memcpy(dst + total, src + i * row_stride, sz);
    total += (int64_t)sz;
  }
  return total;
}

// ---------------------------------------------------------------- records
// Kafka v2 record framing: zigzag varints, LSB-group-first.
static inline int64_t zz_decode(uint64_t u) {
  return (int64_t)(u >> 1) ^ -(int64_t)(u & 1);
}

static inline const uint8_t* read_uvarint(const uint8_t* p, const uint8_t* end,
                                          uint64_t* out) {
  uint64_t result = 0;
  int shift = 0;
  while (p < end && shift <= 63) {
    uint8_t b = *p++;
    result |= (uint64_t)(b & 0x7F) << shift;
    if (!(b & 0x80)) {
      *out = result;
      return p;
    }
    shift += 7;
  }
  return nullptr;
}

static inline uint8_t* write_zigzag(uint8_t* p, int64_t v) {
  uint64_t u = ((uint64_t)v << 1) ^ (uint64_t)(v >> 63);
  while (u >= 0x80) {
    *p++ = (uint8_t)(u | 0x80);
    u >>= 7;
  }
  *p++ = (uint8_t)u;
  return p;
}

// Parse `count` varint-framed records from a batch payload; emit each
// record's value offset/length (-1 length for null values). Returns the
// number of records parsed (== count on success).
// Walk ONE record's framing from *pp; on success advance *pp past the
// record and emit the value span (vlen -1 = null value). Shared by the
// split parse (rp_parse_record_values) and the fused explode+find — the
// framing rules must not be able to diverge between them.
static inline bool parse_one_record(const uint8_t** pp, const uint8_t* end,
                                    const uint8_t** value_out,
                                    int64_t* vlen_out) {
  const uint8_t* p = *pp;
  uint64_t u;
  p = read_uvarint(p, end, &u);
  if (!p) return false;
  int64_t body_len = zz_decode(u);
  const uint8_t* body_end = p + body_len;
  if (body_len < 0 || body_end > end) return false;
  if (p >= body_end) return false;
  p++;  // attributes
  if (!(p = read_uvarint(p, body_end, &u))) return false;  // ts delta
  if (!(p = read_uvarint(p, body_end, &u))) return false;  // offset delta
  if (!(p = read_uvarint(p, body_end, &u))) return false;  // key len
  int64_t klen = zz_decode(u);
  if (klen > 0) p += klen;
  if (p > body_end) return false;
  if (!(p = read_uvarint(p, body_end, &u))) return false;  // value len
  int64_t vlen = zz_decode(u);
  if (vlen >= 0 && p + vlen > body_end) return false;
  *value_out = p;
  *vlen_out = vlen;
  *pp = body_end;  // skip headers
  return true;
}

int32_t rp_parse_record_values(const uint8_t* payload, size_t payload_len,
                               int32_t count, int64_t* val_off,
                               int32_t* val_len) {
  const uint8_t* p = payload;
  const uint8_t* end = payload + payload_len;
  for (int32_t i = 0; i < count; i++) {
    const uint8_t* value;
    int64_t vlen;
    if (!parse_one_record(&p, end, &value, &vlen)) return i;
    val_off[i] = value - payload;
    val_len[i] = vlen < 0 ? -1 : (int32_t)vlen;
  }
  return count;
}

// Parse MANY batches' record values in one call (the engine's explode
// stage: one ctypes crossing per launch instead of one per batch).
// joined = concatenated batch payloads; for batch b, payload bytes are
// joined[payload_off[b] .. +payload_len[b]) holding counts[b] records.
// Emits val_off (absolute into joined) / val_len flattened in batch order.
// Returns the number of records parsed (== sum(counts) on success).
int64_t rp_parse_many(const uint8_t* joined, const int64_t* payload_off,
                      const int32_t* payload_len, const int32_t* counts,
                      int32_t n_batches, int64_t* val_off, int32_t* val_len) {
  int64_t k = 0;
  for (int32_t b = 0; b < n_batches; b++) {
    int32_t parsed = rp_parse_record_values(
        joined + payload_off[b], (size_t)payload_len[b], counts[b],
        val_off + k, val_len + k);
    if (parsed != counts[b]) return k + parsed;
    for (int32_t i = 0; i < counts[b]; i++) val_off[k + i] += payload_off[b];
    k += counts[b];
  }
  return k;
}

// rp_parse_many's pointer-table twin (the payload staging lane's explode):
// batch b's payload is payloads[b][0 .. payload_len[b]) and its records'
// val_off stay RELATIVE to that buffer, so no joined blob is needed. One
// crossing a launch, where the lane made one rp_parse_record_values call a
// batch. sizes[i] is val_len[i] clamped at 0 (a null value stages as
// empty). Returns the number of records parsed (== sum(counts) on success).
int64_t rp_parse_many_ptrs(const uint8_t* const* payloads,
                           const int64_t* payload_len, const int32_t* counts,
                           int64_t n_batches, int64_t* val_off,
                           int32_t* val_len, int32_t* sizes) {
  int64_t k = 0;
  for (int64_t b = 0; b < n_batches; b++) {
    int32_t parsed = rp_parse_record_values(
        payloads[b], (size_t)payload_len[b], counts[b], val_off + k,
        val_len + k);
    if (parsed != counts[b]) return k + parsed;
    k += counts[b];
  }
  for (int64_t i = 0; i < k; i++) sizes[i] = val_len[i] < 0 ? 0 : val_len[i];
  return k;
}

// ------------------------------------------------- zstd, many frames a call
// A launch's compressed batches decompress in ONE crossing (no interpreter
// lock is held inside a ctypes call) into memory the caller owns and
// reuses. libzstd is resolved at run time, as compression/codecs.py
// resolves liblz4 and libsnappy: a host without it builds and loads this
// library all the same, rp_zstd_available() says 0 there and callers keep
// the per-batch codec.
typedef struct ZSTD_DCtx_s ZSTD_DCtx;
typedef struct ZSTD_CCtx_s ZSTD_CCtx;
static struct {
  ZSTD_DCtx* (*create)();
  size_t (*free)(ZSTD_DCtx*);
  size_t (*decompress)(ZSTD_DCtx*, void*, size_t, const void*, size_t);
  unsigned long long (*content_size)(const void*, size_t);
  size_t (*frame_size)(const void*, size_t);
  unsigned (*is_error)(size_t);
  bool ok;
  // the way out (rp_seal_many): a libzstd without these still decompresses
  ZSTD_CCtx* (*ccreate)();
  size_t (*cfree)(ZSTD_CCtx*);
  size_t (*compress)(ZSTD_CCtx*, void*, size_t, const void*, size_t, int);
  size_t (*bound)(size_t);
  bool can_compress;
} zstd;
static std::once_flag zstd_once;

static void zstd_resolve() {
  void* h = nullptr;
  for (const char* name : {"libzstd.so.1", "libzstd.so"}) {
    if ((h = dlopen(name, RTLD_NOW | RTLD_LOCAL))) break;
  }
  if (!h) return;
  *(void**)&zstd.create = dlsym(h, "ZSTD_createDCtx");
  *(void**)&zstd.free = dlsym(h, "ZSTD_freeDCtx");
  *(void**)&zstd.decompress = dlsym(h, "ZSTD_decompressDCtx");
  *(void**)&zstd.content_size = dlsym(h, "ZSTD_getFrameContentSize");
  *(void**)&zstd.frame_size = dlsym(h, "ZSTD_findFrameCompressedSize");
  *(void**)&zstd.is_error = dlsym(h, "ZSTD_isError");
  zstd.ok = zstd.create && zstd.free && zstd.decompress &&
            zstd.content_size && zstd.frame_size && zstd.is_error;
  *(void**)&zstd.ccreate = dlsym(h, "ZSTD_createCCtx");
  *(void**)&zstd.cfree = dlsym(h, "ZSTD_freeCCtx");
  *(void**)&zstd.compress = dlsym(h, "ZSTD_compressCCtx");
  *(void**)&zstd.bound = dlsym(h, "ZSTD_compressBound");
  zstd.can_compress = zstd.is_error && zstd.ccreate && zstd.cfree &&
                      zstd.compress && zstd.bound;
}

int32_t rp_zstd_available() {
  std::call_once(zstd_once, zstd_resolve);
  return zstd.ok ? 1 : 0;
}

// The size frame b's header states (out_len[b]), -1 where it states none
// (a streaming producer's frame) or the header does not parse: those
// frames are the per-batch codec's, which has no fixed output cap and
// raises on garbage. out_off[b] is where frame b goes in one buffer that
// holds the sized frames back to back. Returns that buffer's size, -1
// without libzstd.
int64_t rp_zstd_frame_sizes(const uint8_t* const* srcs,
                            const int64_t* src_lens, int64_t n,
                            int64_t* out_off, int64_t* out_len) {
  if (!rp_zstd_available()) return -1;
  int64_t total = 0;
  for (int64_t b = 0; b < n; b++) {
    unsigned long long sz = zstd.content_size(srcs[b], (size_t)src_lens[b]);
    out_off[b] = total;
    // ZSTD_CONTENTSIZE_UNKNOWN / _ERROR are the two largest values
    if (sz >= (unsigned long long)INT64_MAX) {
      out_len[b] = -1;
    } else {
      out_len[b] = (int64_t)sz;
      total += (int64_t)sz;
    }
  }
  return total;
}

// Run `work` (which claims its items off a shared counter until none are
// left) on the caller's thread and on up to n_threads - 1 more, none below
// 32 of the n items a thread: a small launch stays on the caller's.
extern "C++" {
template <typename Work>
static void run_split(Work& work, int64_t n, int32_t n_threads) {
  int32_t extra = n_threads > 1 ? n_threads - 1 : 0;
  if ((int64_t)extra > n / 32) extra = (int32_t)(n / 32);
  std::vector<std::thread> pool;
  pool.reserve((size_t)extra);
  for (int32_t t = 0; t < extra; t++) {
    try {
      pool.emplace_back(std::ref(work));
    } catch (...) {
      break;  // no thread to be had: the caller's does the rest
    }
  }
  work();
  for (auto& t : pool) t.join();
}
}  // extern "C++"

// Decompress frame b (dst_len[b] >= 0) into dst + dst_off[b]; frames with
// dst_len[b] < 0 are skipped. Only the FIRST frame of srcs[b] is read,
// which is what the per-batch codec (one decompressobj a batch) decodes.
// n_threads > 1 splits the frames over that many threads, the caller's
// among them, each with a context of its own. A frame that fails, or does
// not fill exactly dst_len[b] bytes, gets dst_len[b] = -1: it is the
// per-batch codec's to decode or to refuse, as it always was. Returns the
// number of such frames; -1 without libzstd or a context; -2, with
// nothing written, when a span lies outside dst[0 .. dst_cap).
int64_t rp_zstd_uncompress_many(const uint8_t* const* srcs,
                                const int64_t* src_lens, int64_t n,
                                uint8_t* dst, int64_t dst_cap,
                                const int64_t* dst_off, int64_t* dst_len,
                                int32_t n_threads) {
  if (!rp_zstd_available()) return -1;
  for (int64_t b = 0; b < n; b++) {
    if (dst_len[b] >= 0 &&
        (dst_off[b] < 0 || dst_off[b] > dst_cap - dst_len[b]))
      return -2;
  }
  std::atomic<int64_t> next{0};
  std::atomic<int64_t> failed{0};
  std::atomic<bool> no_ctx{false};
  auto work = [&]() {
    // one context a thread for the life of the thread: the engine's
    // dispatch thread comes back every launch
    static thread_local struct Ctx {
      ZSTD_DCtx* c = nullptr;
      ~Ctx() { if (c) zstd.free(c); }
    } ctx;
    if (!ctx.c && !(ctx.c = zstd.create())) {
      no_ctx.store(true);
      return;
    }
    const int64_t kChunk = 16;  // frames a claim: few atomics, even split
    for (;;) {
      int64_t lo = next.fetch_add(kChunk);
      if (lo >= n) return;
      int64_t hi = lo + kChunk < n ? lo + kChunk : n;
      for (int64_t b = lo; b < hi; b++) {
        if (dst_len[b] < 0) continue;
        size_t clen = zstd.frame_size(srcs[b], (size_t)src_lens[b]);
        size_t got = zstd.is_error(clen)
                         ? clen
                         : zstd.decompress(ctx.c, dst + dst_off[b],
                                           (size_t)dst_len[b], srcs[b], clen);
        if (zstd.is_error(got) || (int64_t)got != dst_len[b]) {
          dst_len[b] = -1;
          failed.fetch_add(1);
        }
      }
    }
  };
  run_split(work, n, n_threads);
  // a thread without a context claimed nothing; if the caller's had none,
  // frames may be left undone
  if (no_ctx.load() && next.load() < n) return -1;
  return failed.load();
}

// ------------------------------------------------- the seal, many a call
// A launch's framed payloads become output batches in ONE crossing (the
// mirror of rp_zstd_uncompress_many on the way out): job b's payload is
// compressed where payload_lens[b] >= threshold and codec is Zstd (one
// frame that states its content size, as ZSTD_compressCCtx writes it and
// as the many-frames decompress needs it), and both CRCs of the batch's
// header are computed as models/record.py computes them: the Kafka CRC
// over the 40-byte big-endian prefix (attributes .. record count) and the
// payload as it is stored, with no joined copy, and the internal header
// CRC over the 57 little-endian bytes after header_crc. What the job's
// header does not carry here is what build_output_batch sets: base offset
// 0, producer id / epoch / base sequence -1.
int32_t rp_seal_available() {
  std::call_once(zstd_once, zstd_resolve);
  return zstd.can_compress ? 1 : 0;
}

static inline uint8_t* put_be(uint8_t* p, uint64_t v, int n) {
  for (int i = n - 1; i >= 0; i--) *p++ = (uint8_t)(v >> (8 * i));
  return p;
}

static inline uint8_t* put_le(uint8_t* p, uint64_t v, int n) {
  for (int i = 0; i < n; i++) *p++ = (uint8_t)(v >> (8 * i));
  return p;
}

// Both header CRCs of a batch build_output_batch would make around
// `stored` (the payload as it goes to the log).
static void seal_crcs(const uint8_t* stored, int64_t stored_len,
                      int32_t attrs, int32_t kept, int8_t type,
                      int64_t first_ts, int64_t max_ts, uint32_t* crc_out,
                      uint32_t* header_crc_out) {
  uint8_t be[40];
  uint8_t* p = put_be(be, (uint16_t)attrs, 2);
  p = put_be(p, (uint32_t)(kept - 1), 4);  // last offset delta
  p = put_be(p, (uint64_t)first_ts, 8);
  p = put_be(p, (uint64_t)max_ts, 8);
  p = put_be(p, ~0ull, 8);                 // producer id -1
  p = put_be(p, 0xFFFFu, 2);               // producer epoch -1
  p = put_be(p, 0xFFFFFFFFu, 4);           // base sequence -1
  p = put_be(p, (uint32_t)kept, 4);        // record count
  uint32_t c = rp_crc32c_update(0xFFFFFFFFu, be, sizeof be);
  uint32_t crc = rp_crc32c_update(c, stored, (size_t)stored_len) ^ 0xFFFFFFFFu;
  uint8_t le[57];
  p = put_le(le, (uint32_t)(61 + stored_len), 4);  // size_bytes
  p = put_le(p, 0, 8);                             // base offset
  *p++ = (uint8_t)type;
  p = put_le(p, crc, 4);
  p = put_le(p, (uint16_t)attrs, 2);
  p = put_le(p, (uint32_t)(kept - 1), 4);
  p = put_le(p, (uint64_t)first_ts, 8);
  p = put_le(p, (uint64_t)max_ts, 8);
  p = put_le(p, ~0ull, 8);
  p = put_le(p, 0xFFFFu, 2);
  p = put_le(p, 0xFFFFFFFFu, 4);
  p = put_le(p, (uint32_t)kept, 4);
  *crc_out = crc;
  *header_crc_out = rp_crc32c(le, sizeof le);
}

// Seal jobs 0 .. n-1. Job b with kept[b] <= 0 makes no batch and is
// skipped (out_len[b] = -1). For every other job, on success: out_len[b]
// is the stored payload's length and out_attrs[b] its attributes; where
// out_attrs[b] != 0 the stored payload is dst[out_off[b] .. + out_len[b])
// (a Zstd frame), where it is 0 it is the job's own payload, untouched
// (out_off[b] = -1); out_crc[b] / out_header_crc[b] are the header's two
// CRCs. A job this call could not seal gets out_len[b] = -1 and is the
// per-batch road's to seal or to refuse, as it always was: its frame's
// bound does not fit what is left of dst, the codec fails, the batch
// would be wider than a header's size field. codec is 0 (store all) or 4
// (Zstd); level is Zstd's. n_threads > 1 splits the jobs over that many
// threads, the caller's among them and none below 32 jobs a thread, each
// with a compression context of its own. Returns the number of jobs it
// could not seal (skipped ones are not among them); -1, and then every
// job is the per-batch road's, for another codec, for Zstd without
// libzstd's compress side, or where the caller's thread gets no context.
int64_t rp_seal_many(const uint8_t* const* payloads,
                     const int64_t* payload_lens, const int32_t* kept,
                     const int8_t* types, const int64_t* first_ts,
                     const int64_t* max_ts, int64_t n, int64_t threshold,
                     int32_t codec, int32_t level, uint8_t* dst,
                     int64_t dst_cap, int64_t* out_off, int64_t* out_len,
                     int32_t* out_attrs, uint32_t* out_crc,
                     uint32_t* out_header_crc, int32_t n_threads) {
  const int32_t kZstd = 4;
  if (codec != 0 && codec != kZstd) return -1;
  if (codec == kZstd && !rp_seal_available()) return -1;
  rp_crc32c_update(0, nullptr, 0);  // the dispatch is picked before threads
  // every frame's place in dst, at its bound, before any thread starts:
  // out_len < 0 marks the jobs that are not to be sealed
  int64_t used = 0;
  for (int64_t b = 0; b < n; b++) {
    out_off[b] = -1;
    out_attrs[b] = 0;
    int64_t len = payload_lens[b];
    if (kept[b] <= 0 || len < 0 || len > INT32_MAX - 61) {
      out_len[b] = -1;
      continue;
    }
    out_len[b] = len;
    if (codec == kZstd && len >= threshold) {
      int64_t cap = (int64_t)zstd.bound((size_t)len);
      if (cap > dst_cap - used) {
        out_len[b] = -1;  // dst too small for this one
        continue;
      }
      out_off[b] = used;
      out_attrs[b] = kZstd;
      used += cap;
    }
  }
  std::atomic<int64_t> next{0};
  std::atomic<bool> no_ctx{false};
  auto work = [&]() {
    static thread_local struct Ctx {
      ZSTD_CCtx* c = nullptr;
      ~Ctx() { if (c) zstd.cfree(c); }
    } ctx;
    if (codec == kZstd && !ctx.c && !(ctx.c = zstd.ccreate())) {
      no_ctx.store(true);
      return;
    }
    const int64_t kChunk = 16;  // jobs a claim: few atomics, even split
    for (;;) {
      int64_t lo = next.fetch_add(kChunk);
      if (lo >= n) return;
      int64_t hi = lo + kChunk < n ? lo + kChunk : n;
      for (int64_t b = lo; b < hi; b++) {
        if (out_len[b] < 0) continue;
        const uint8_t* stored = payloads[b];
        int64_t stored_len = out_len[b];
        if (out_attrs[b]) {
          size_t cap = zstd.bound((size_t)stored_len);
          size_t got = zstd.compress(ctx.c, dst + out_off[b], cap, stored,
                                     (size_t)stored_len, level);
          if (zstd.is_error(got)) {
            out_len[b] = -1;
            continue;
          }
          stored = dst + out_off[b];
          stored_len = (int64_t)got;
          out_len[b] = stored_len;
        }
        seal_crcs(stored, stored_len, out_attrs[b], kept[b], types[b],
                  first_ts[b], max_ts[b], out_crc + b, out_header_crc + b);
      }
    }
  };
  run_split(work, n, n_threads);
  // a thread without a context claimed nothing; if the caller's had none,
  // jobs may be left undone
  if (no_ctx.load() && next.load() < n) return -1;
  int64_t failed = 0;
  for (int64_t b = 0; b < n; b++)
    if (out_len[b] < 0 && kept[b] > 0) failed++;
  return failed;
}

// ---------------------------------------------------------------- append
static inline uint32_t get_le32(const uint8_t* p) {
  return (uint32_t)p[0] | (uint32_t)p[1] << 8 | (uint32_t)p[2] << 16 |
         (uint32_t)p[3] << 24;
}

// The log's offset-assigning append of a list of batches, one crossing a
// list (storage/log.py): what RecordBatch.with_base_offset(..)
// .encode_internal() writes a batch, and verify_kafka_crc() where asked.
// heads holds the n batches' 61-byte internal headers as the batches state
// them (RecordBatchHeader.encode; base offset and header_crc are not
// read); payloads[b] holds the size_bytes - 61 bytes header b states (the
// caller has checked each length). Batch b is framed at dst + pos, frames
// back to back: its header with base offset `next` and the header_crc over
// the 57 bytes after it, then its payload, copied once; out_header_crc[b]
// is that header_crc and `next` moves on by last_offset_delta + 1. With
// verify != 0 a batch whose Kafka CRC over attrs..records (the big-endian
// header prefix, then the payload) is not the header's crc is left out:
// out_header_crc[b] = -1, no frame, no offset taken, its neighbours
// contiguous. Returns the bytes written, or -1, with dst nobody's, for a
// size_bytes under 61 or a frame that dst_cap does not hold.
// Single-threaded by design: a list is ~10 us of memcpy and CRC.
int64_t rp_frame_internal_many(const uint8_t* heads,
                               const uint8_t* const* payloads, int64_t n,
                               int64_t first_base_offset, int32_t verify,
                               uint8_t* dst, int64_t dst_cap,
                               int64_t* out_header_crc) {
  // attrs, last offset delta, the two timestamps, producer id / epoch /
  // base sequence, record count: header bytes 21..61, each field reversed
  static const int kPrefixWidths[8] = {2, 4, 8, 8, 8, 2, 4, 4};
  int64_t pos = 0;
  int64_t next = first_base_offset;
  for (int64_t b = 0; b < n; b++) {
    const uint8_t* h = heads + 61 * b;
    int64_t size = (int64_t)(int32_t)get_le32(h + 4);
    if (size < 61 || size > dst_cap - pos) return -1;
    size_t len = (size_t)(size - 61);
    if (verify) {
      uint8_t be[40];
      uint8_t* p = be;
      const uint8_t* f = h + 21;
      for (int w : kPrefixWidths) {
        for (int i = 0; i < w; i++) p[i] = f[w - 1 - i];
        p += w;
        f += w;
      }
      uint32_t c = rp_crc32c_update(0xFFFFFFFFu, be, sizeof be);
      c = rp_crc32c_update(c, payloads[b], len) ^ 0xFFFFFFFFu;
      if (c != get_le32(h + 17)) {
        out_header_crc[b] = -1;
        continue;
      }
    }
    uint8_t* o = dst + pos;
    memcpy(o, h, 61);
    put_le(o + 8, (uint64_t)next, 8);
    uint32_t header_crc = rp_crc32c(o + 4, 57);
    put_le(o, header_crc, 4);
    memcpy(o + 61, payloads[b], len);
    out_header_crc[b] = (int64_t)header_crc;
    next += (int64_t)(int32_t)get_le32(h + 23) + 1;
    pos += size;
  }
  return pos;
}

// ---------------------------------------------------------------- scan
static inline int64_t get_le_signed(const uint8_t* p, int width) {
  uint64_t v = 0;
  for (int i = 0; i < width; i++) v |= (uint64_t)p[i] << (8 * i);
  if (width < 8 && (v >> (8 * width - 1)) & 1) v |= ~(uint64_t)0 << (8 * width);
  return (int64_t)v;
}

// A scanning read's walk over the internal frames of a window of a segment
// file, one crossing a window (storage/segment.py Segment.scan): what
// _FrameReader + RecordBatch.decode_internal + scan's three rules do a
// frame. From win + at on, frame after frame: a frame is taken only whole
// (its 61-byte header and its size_bytes inside win_len) and sound
// (size_bytes >= 61, a type in known_types, header_crc == CRC-32C of header
// bytes 4..61), asked in the reader's order; then scan's rules in scan's
// order: a base offset past max_offset ends the walk with the frame NOT
// consumed; a last offset under start_offset, or a type outside type_mask,
// consumes the frame and keeps nothing; a kept frame writes one row (its
// position in win, then the thirteen header fields, header_crc, crc and
// attrs unsigned) and counts its size_bytes against budget, which ends the
// walk once taken.
// table: int64[4 + 14 * rows_cap]; table[0] rows written, table[1] the
// position the walk stopped at (every frame before it consumed), table[2]
// the position just past the last kept frame (-1: none kept), table[3] the
// bytes kept; rows from table[4] on. Returns why the walk stopped: 0 done
// (budget or max_offset), 1 the window ends inside the frame at table[1]
// (or holds no more), 2 that frame is not sound (the caller decodes it the
// slow way for the error), 3 rows_cap rows are written.
int32_t rp_scan_internal_frames(const uint8_t* win, int64_t win_len,
                                int64_t at, int64_t start_offset,
                                int64_t max_offset, int64_t budget,
                                uint64_t known_types, uint64_t type_mask,
                                int64_t* table, int64_t rows_cap) {
  // the fields after size_bytes: base offset, type, crc, attrs, last offset
  // delta, the two timestamps, producer id / epoch, base sequence, records
  static const int kWidths[11] = {8, 1, 4, 2, 4, 8, 8, 8, 2, 4, 4};
  int64_t n = 0, kept_end = -1, taken = 0;
  int32_t status;
  for (;;) {
    if (win_len - at < 61) { status = 1; break; }
    const uint8_t* h = win + at;
    int64_t size = (int64_t)(int32_t)get_le32(h + 4);
    if (size >= 61 && win_len - at < size) { status = 1; break; }
    int type = (int)(int8_t)h[16];
    uint32_t header_crc = get_le32(h);
    if (size < 61 || type < 0 || type > 63 || !(known_types >> type & 1) ||
        rp_crc32c(h + 4, 57) != header_crc) {
      status = 2;
      break;
    }
    int64_t base = get_le_signed(h + 8, 8);
    int64_t last = (int64_t)((uint64_t)base + (uint64_t)get_le_signed(h + 23, 4));
    if (base > max_offset) { status = 0; break; }
    if (last < start_offset || !(type_mask >> type & 1)) {
      at += size;
      continue;
    }
    if (n == rows_cap) { status = 3; break; }
    int64_t* row = table + 4 + 14 * n++;
    row[0] = at;
    row[1] = (int64_t)header_crc;
    row[2] = size;
    const uint8_t* f = h + 8;
    for (int i = 0; i < 11; i++) {
      row[3 + i] = get_le_signed(f, kWidths[i]);
      f += kWidths[i];
    }
    row[5] &= 0xFFFFFFFFll;  // crc and attrs: unsigned, as
    row[6] &= 0xFFFFll;      // RecordBatchHeader.decode gives them
    at += size;
    kept_end = at;
    taken += size;
    if (taken >= budget) { status = 0; break; }
  }
  table[0] = n;
  table[1] = at;
  table[2] = kept_end;
  table[3] = taken;
  return status;
}

// Build a records payload from kept transform outputs: record i (where
// keep[i] != 0) becomes {attrs=0, ts_delta=0, offset_delta=seq, key=null,
// value=rows[i][:lens[i]], headers=0}. Writes payload to dst (caller sizes
// it at n * (row_stride + 16)); returns payload byte length, and the number
// of kept records via *kept_out.
int64_t rp_frame_records(const uint8_t* rows, size_t row_stride,
                         const int32_t* lens, const uint8_t* keep, int32_t n,
                         uint8_t* dst, int32_t* kept_out) {
  uint8_t* out = dst;
  int32_t seq = 0;
  uint8_t body_buf[16];
  for (int32_t i = 0; i < n; i++) {
    if (!keep[i]) continue;
    int32_t vlen = lens[i] < 0 ? 0 : lens[i];
    if ((size_t)vlen > row_stride) vlen = (int32_t)row_stride;
    // body = attrs(1) + ts_delta + offset_delta + key_len(-1) + value_len +
    //        value + header_count
    uint8_t* b = body_buf;
    *b++ = 0;                      // attributes
    b = write_zigzag(b, 0);        // timestamp delta
    b = write_zigzag(b, seq);      // offset delta
    b = write_zigzag(b, -1);       // null key
    b = write_zigzag(b, vlen);     // value length
    size_t pre_len = (size_t)(b - body_buf);
    int64_t body_len = (int64_t)pre_len + vlen + 1;  // +1 header count
    out = write_zigzag(out, body_len);
    std::memcpy(out, body_buf, pre_len);
    out += pre_len;
    std::memcpy(out, rows + (size_t)i * row_stride, vlen);
    out += vlen;
    out = write_zigzag(out, 0);    // header count
    seq++;
  }
  *kept_out = seq;
  return out - dst;
}

// Frame MANY batch ranges in one crossing (one ctypes call per LAUNCH
// instead of one per batch — the per-call Python/ctypes overhead was the
// single biggest host cost at 32-record batches). For each range r,
// records [starts[r], ends[r]) are framed contiguously into dst;
// out_off/out_len give the payload slice and out_kept the surviving
// record count per range. Returns total bytes written.
int64_t rp_frame_many(const uint8_t* rows, size_t row_stride,
                      const int32_t* lens, const uint8_t* keep,
                      const int64_t* starts, const int64_t* ends,
                      int64_t n_ranges, uint8_t* dst,
                      int64_t* out_off, int64_t* out_len,
                      int32_t* out_kept) {
  uint8_t* out = dst;
  uint8_t body_buf[16];
  for (int64_t r = 0; r < n_ranges; r++) {
    uint8_t* range_start = out;
    int32_t seq = 0;
    for (int64_t i = starts[r]; i < ends[r]; i++) {
      if (!keep[i]) continue;
      int32_t vlen = lens[i] < 0 ? 0 : lens[i];
      if ((size_t)vlen > row_stride) vlen = (int32_t)row_stride;
      uint8_t* b = body_buf;
      *b++ = 0;                      // attributes
      b = write_zigzag(b, 0);        // timestamp delta
      b = write_zigzag(b, seq);      // offset delta
      b = write_zigzag(b, -1);       // null key
      b = write_zigzag(b, vlen);     // value length
      size_t pre_len = (size_t)(b - body_buf);
      int64_t body_len = (int64_t)pre_len + vlen + 1;  // +1 header count
      out = write_zigzag(out, body_len);
      std::memcpy(out, body_buf, pre_len);
      out += pre_len;
      std::memcpy(out, rows + (size_t)i * row_stride, vlen);
      out += vlen;
      out = write_zigzag(out, 0);    // header count
      seq++;
    }
    out_off[r] = range_start - dst;
    out_len[r] = out - range_start;
    out_kept[r] = seq;
  }
  return out - dst;
}

// One record framed into the output stream: {attrs=0, ts_delta=0,
// offset_delta=seq, key=null, value=value[0:vlen], headers=0}. The ONE
// framing layout shared by the gather path (values straight out of a
// source blob) — byte-for-byte the layout rp_frame_records/rp_frame_many
// emit from padded rows, which the gather parity tests pin down.
static inline uint8_t* frame_one(uint8_t* out, const uint8_t* value,
                                 int32_t vlen, int32_t seq) {
  uint8_t body_buf[16];
  uint8_t* b = body_buf;
  *b++ = 0;                      // attributes
  b = write_zigzag(b, 0);        // timestamp delta
  b = write_zigzag(b, seq);      // offset delta
  b = write_zigzag(b, -1);       // null key
  b = write_zigzag(b, vlen);     // value length
  size_t pre_len = (size_t)(b - body_buf);
  int64_t body_len = (int64_t)pre_len + vlen + 1;  // +1 header count
  out = write_zigzag(out, body_len);
  std::memcpy(out, body_buf, pre_len);
  out += pre_len;
  std::memcpy(out, value, (size_t)vlen);
  out += vlen;
  out = write_zigzag(out, 0);    // header count
  return out;
}

// ZERO-COPY framing: build a records payload for kept records straight
// from a source blob via per-record (offset, len) columns — no padded
// [n, stride] row matrix ever exists; the one memcpy per record IS the
// framed output. lens[i] < 0 (null value) frames as an empty value,
// matching the padded path's clamp. Caller sizes dst at
// sum(max(lens,0)) + 16*n + 16; returns payload length, kept via
// *kept_out.
int64_t rp_frame_gather(const uint8_t* src, const int64_t* offsets,
                        const int32_t* lens, const uint8_t* keep, int64_t n,
                        uint8_t* dst, int32_t* kept_out) {
  uint8_t* out = dst;
  int32_t seq = 0;
  for (int64_t i = 0; i < n; i++) {
    if (!keep[i]) continue;
    int32_t vlen = lens[i] < 0 ? 0 : lens[i];
    out = frame_one(out, src + offsets[i], vlen, seq);
    seq++;
  }
  *kept_out = seq;
  return out - dst;
}

// Gather-frame MANY record ranges in one crossing (the launch-wide twin of
// rp_frame_many for the zero-copy path): for each range r, kept records
// [starts[r], ends[r]) frame contiguously into dst via rp_frame_gather
// (one range = one rp_frame_gather call, so the two symbols cannot
// diverge); out_off/out_len give the payload slice and out_kept the
// surviving count per range. Returns total bytes written.
int64_t rp_frame_many_gather(const uint8_t* src, const int64_t* offsets,
                             const int32_t* lens, const uint8_t* keep,
                             const int64_t* starts, const int64_t* ends,
                             int64_t n_ranges, uint8_t* dst,
                             int64_t* out_off, int64_t* out_len,
                             int32_t* out_kept) {
  int64_t total = 0;
  for (int64_t r = 0; r < n_ranges; r++) {
    int64_t s = starts[r];
    out_off[r] = total;
    out_len[r] = rp_frame_gather(src, offsets + s, lens + s, keep + s,
                                 ends[r] - s, dst + total, out_kept + r);
    total += out_len[r];
  }
  return total;
}

// The pointer-table twin of rp_frame_many_gather: range r's records take
// their (offset, len) RELATIVE to their own source buffer srcs[r] (one
// range = one input batch = one retained decompressed payload buffer, the
// payload staging lane's PtrExploded), so kept values frame straight from
// those buffers and no joined blob is ever built. Every record of every
// range is bounds-checked against src_lens[r] BEFORE anything is read or
// written; returns -1 on a span outside its buffer, else total bytes.
int64_t rp_frame_many_gather_ptrs(const uint8_t* const* srcs,
                                  const int64_t* src_lens,
                                  const int64_t* offsets, const int32_t* lens,
                                  const uint8_t* keep, const int64_t* starts,
                                  const int64_t* ends, int64_t n_ranges,
                                  uint8_t* dst, int64_t* out_off,
                                  int64_t* out_len, int32_t* out_kept) {
  for (int64_t r = 0; r < n_ranges; r++) {
    for (int64_t i = starts[r]; i < ends[r]; i++) {
      int64_t vlen = lens[i] < 0 ? 0 : lens[i];
      if (offsets[i] < 0 || offsets[i] + vlen > src_lens[r]) return -1;
    }
  }
  int64_t total = 0;
  for (int64_t r = 0; r < n_ranges; r++) {
    int64_t s = starts[r];
    out_off[r] = total;
    out_len[r] = rp_frame_gather(srcs[r], offsets + s, lens + s, keep + s,
                                 ends[r] - s, dst + total, out_kept + r);
    total += out_len[r];
  }
  return total;
}

// ---------------------------------------------------------------- columnar
// JSON field extraction for the columnar pushdown path (coproc engine v2).
// The device link charges per byte (tools/link_probe.py measures it), so
// the engine ships *columns* of the fields a compiled TransformSpec
// references instead of record payloads.
// This walker mirrors redpanda_tpu/ops/exprs.py json_find byte-for-byte:
// parity is tested in tests/test_exprs.py (TestNativeWalkerParity).

static inline int64_t skip_ws(const uint8_t* s, int64_t i, int64_t end) {
  while (i < end && (s[i] == ' ' || s[i] == '\t' || s[i] == '\n' || s[i] == '\r'))
    i++;
  return i;
}

static int64_t skip_string(const uint8_t* s, int64_t i, int64_t end) {
  // memchr-accelerated: jump to each '"' and check whether it is escaped
  // (odd run of preceding backslashes). Equivalent to the byte-stepping
  // Python reference (ops/exprs.py _skip_string) on every input.
  i++;  // opening quote
  while (i < end) {
    const uint8_t* q =
        (const uint8_t*)std::memchr(s + i, '"', (size_t)(end - i));
    if (!q) return end;
    int64_t qi = q - s;
    int64_t bs = qi - 1;
    while (bs >= i && s[bs] == '\\') bs--;
    if (((qi - 1 - bs) & 1) == 0) return qi + 1;  // even backslashes: closes
    i = qi + 1;
  }
  return end;
}

static int64_t skip_value(const uint8_t* s, int64_t i, int64_t end) {
  i = skip_ws(s, i, end);
  if (i >= end) return end;
  uint8_t c = s[i];
  if (c == '"') return skip_string(s, i, end);
  if (c == '{' || c == '[') {
    int depth = 0;
    while (i < end) {
      c = s[i];
      if (c == '"') {
        i = skip_string(s, i, end);
        continue;
      }
      if (c == '{' || c == '[') depth++;
      else if (c == '}' || c == ']') {
        depth--;
        if (depth == 0) return i + 1;
      }
      i++;
    }
    return end;
  }
  while (i < end && c != ',' && c != '}' && c != ']' && c != ' ' && c != '\t' &&
         c != '\n' && c != '\r') {
    i++;
    if (i < end) c = s[i];
  }
  return i;
}

// Classify the value starting at s[i]; returns the type (0 missing,
// 1 string, 2 number, 3 true, 4 false, 5 null, 6 object, 7 array) and
// fills vs/ve (string extent excludes quotes). The ONE classification
// used by rp_json_find and rp_find_multi alike.
static int32_t classify_value(const uint8_t* s, int64_t i, int64_t end,
                              int64_t* vs, int64_t* ve) {
  if (i >= end) return 0;
  uint8_t c = s[i];
  if (c == '"') {
    int64_t j = skip_string(s, i, end);
    *vs = i + 1;
    *ve = j - 1;
    return 1;
  }
  if (c == '{') {
    *vs = i;
    *ve = skip_value(s, i, end);
    return 6;
  }
  if (c == '[') {
    *vs = i;
    *ve = skip_value(s, i, end);
    return 7;
  }
  int64_t j = skip_value(s, i, end);
  *vs = i;
  *ve = j;
  int64_t tl = j - i;
  if (tl == 4 && std::memcmp(s + i, "true", 4) == 0) return 3;
  if (tl == 5 && std::memcmp(s + i, "false", 5) == 0) return 4;
  if (tl == 4 && std::memcmp(s + i, "null", 4) == 0) return 5;
  return 2;
}

// Locate dot-separated `path` in JSON object s[0:len]. Returns type
// (0 missing, 1 string, 2 number, 3 true, 4 false, 5 null, 6 object,
// 7 array) and value extent via vs/ve (string extent excludes quotes).
int32_t rp_json_find(const uint8_t* s, int64_t len, const char* path,
                     int32_t path_len, int64_t* vs, int64_t* ve) {
  int64_t i = 0, end = len;
  int32_t seg_start = 0;
  for (;;) {
    int32_t seg_end = seg_start;
    while (seg_end < path_len && path[seg_end] != '.') seg_end++;
    int32_t seg_len = seg_end - seg_start;
    const char* seg = path + seg_start;
    bool last = seg_end >= path_len;

    i = skip_ws(s, i, end);
    if (i >= end || s[i] != '{') return 0;
    i++;
    for (;;) {
      i = skip_ws(s, i, end);
      if (i >= end || s[i] == '}') return 0;
      if (s[i] != '"') return 0;  // malformed
      int64_t kstart = i + 1;
      i = skip_string(s, i, end);
      int64_t kend = i - 1;
      i = skip_ws(s, i, end);
      if (i >= end || s[i] != ':') return 0;
      i++;
      i = skip_ws(s, i, end);
      if (kend - kstart == seg_len &&
          std::memcmp(s + kstart, seg, (size_t)seg_len) == 0) {
        break;  // found this segment; i is at the value start
      }
      i = skip_value(s, i, end);
      i = skip_ws(s, i, end);
      if (i < end && s[i] == ',') i++;
    }
    if (!last) {
      seg_start = seg_end + 1;
      continue;  // descend: value must parse as an object
    }
    return classify_value(s, i, end, vs, ve);
  }
}

// Extract a string-typed field into a [n, w] byte column (zero padded) plus
// per-record raw value length (clipped to 1<<30): -1 = field missing or not
// a string. Bytes are the value's raw JSON bytes (no unescaping), truncated
// to w. Returns number of records with the field present as a string.
int64_t rp_extract_str(const uint8_t* joined, const int64_t* offsets,
                       const int32_t* sizes, int64_t n, const char* path,
                       int32_t path_len, int32_t w, uint8_t* out_bytes,
                       int32_t* out_vlen) {
  int64_t hits = 0;
  for (int64_t i = 0; i < n; i++) {
    uint8_t* dst = out_bytes + i * (int64_t)w;
    std::memset(dst, 0, (size_t)w);
    int32_t sz = sizes[i];
    if (sz <= 0) {
      out_vlen[i] = -1;
      continue;
    }
    int64_t vs, ve;
    int32_t t = rp_json_find(joined + offsets[i], sz, path, path_len, &vs, &ve);
    if (t != 1) {
      out_vlen[i] = -1;
      continue;
    }
    int64_t vlen = ve - vs;
    // a record truncated inside an unterminated string yields ve < vs;
    // clamp to an empty-but-present value (memcpy with (size_t)-1 would
    // corrupt the heap)
    if (vlen < 0) vlen = 0;
    if (vlen > (1 << 30)) vlen = 1 << 30;
    out_vlen[i] = (int32_t)vlen;
    int64_t cp = vlen < w ? vlen : w;
    std::memcpy(dst, joined + offsets[i] + vs, (size_t)cp);
    hits++;
  }
  return hits;
}

// Numeric lattice flags; keep in sync with redpanda_tpu/ops/exprs.py.
enum {
  RP_F_PRESENT = 1,
  RP_F_NUMBER = 2,
  RP_F_INT_EXACT = 4,
  RP_F_BOOL = 8,
  RP_F_NULL = 16,
};

// Shared numeric classification from a found (type, vs, ve) span —
// extract_num and gather_num MUST agree byte-for-byte (parity contract
// with the Python oracle, ops/exprs.py host_field).
static void num_from_span(const uint8_t* rec, int32_t t, int64_t vs,
                          int64_t ve, float* out_f32, int32_t* out_i32,
                          uint8_t* out_flags) {
  *out_f32 = 0.0f;
  *out_i32 = 0;
  *out_flags = 0;
  if (t == 0) return;
  if (t == 3) {  // true
    *out_f32 = 1.0f;
    *out_i32 = 1;
    *out_flags = RP_F_PRESENT | RP_F_BOOL;
  } else if (t == 4) {  // false
    *out_flags = RP_F_PRESENT | RP_F_BOOL;
  } else if (t == 5) {  // null
    *out_flags = RP_F_PRESENT | RP_F_NULL;
  } else if (t == 2) {  // number
    char buf[48];
    int64_t tl = ve - vs;
    // Restrict to decimal-number characters BEFORE strtod: strtod also
    // accepts hex (0x10) / inf / nan, which the Python oracle rejects.
    bool decimal_chars = tl > 0;
    for (int64_t k = 0; k < tl && decimal_chars; k++) {
      uint8_t c = rec[vs + k];
      decimal_chars = (c >= '0' && c <= '9') || c == '-' || c == '+' ||
                      c == '.' || c == 'e' || c == 'E';
    }
    if (decimal_chars && tl < (int64_t)sizeof(buf)) {
      std::memcpy(buf, rec + vs, (size_t)tl);
      buf[tl] = 0;
      char* endp = nullptr;
      double d = strtod(buf, &endp);
      if (endp == buf + tl) {
        *out_f32 = (float)d;
        uint8_t fl = RP_F_PRESENT | RP_F_NUMBER;
        if (std::isfinite(d) && d == (double)(int64_t)d &&
            d >= -2147483648.0 && d <= 2147483647.0) {
          fl |= RP_F_INT_EXACT;
          *out_i32 = (int32_t)d;
        }
        *out_flags = fl;
      } else {
        *out_flags = RP_F_PRESENT;  // malformed number token
      }
    } else {
      *out_flags = RP_F_PRESENT;  // token too long for exact parse
    }
  } else {  // string/object/array
    *out_flags = RP_F_PRESENT;
  }
}

// Single pass over each record's TOP-LEVEL object: span tables for k
// single-segment paths in ONE walk instead of one rp_json_find per path
// (the engine's specs typically reference 2-4 fields of the same record).
// types/vs/ve are [n, k] row-major; type 0 = missing. First occurrence of
// a duplicate key wins, matching rp_json_find's scan order.
// One record's top-level JSON walk locating all k paths; writes one row of
// the span tables. Shared by rp_find_multi (standalone pass) and
// rp_explode_find (fused framing-parse + find, cache-hot).
static void find_in_record(const uint8_t* s, int64_t end,
                           const char* paths_blob, const int32_t* path_off,
                           const int32_t* path_lens, int32_t k, int8_t* trow,
                           int64_t* vrow, int64_t* erow) {
    std::memset(trow, 0, (size_t)k);
    if (end <= 0) return;
    int64_t i = skip_ws(s, 0, end);
    if (i >= end || s[i] != '{') return;
    i++;
    int32_t found = 0;
    for (;;) {
      i = skip_ws(s, i, end);
      if (i >= end || s[i] == '}') break;
      if (s[i] != '"') break;  // malformed
      int64_t kstart = i + 1;
      i = skip_string(s, i, end);
      int64_t kend = i - 1;
      i = skip_ws(s, i, end);
      if (i >= end || s[i] != ':') break;
      i++;
      i = skip_ws(s, i, end);
      int64_t klen = kend - kstart;
      bool matched = false;
      for (int32_t p = 0; p < k; p++) {
        if (trow[p] != 0) continue;  // first occurrence wins
        if (klen == path_lens[p] &&
            std::memcmp(s + kstart, paths_blob + path_off[p],
                        (size_t)path_lens[p]) == 0) {
          int64_t vs, ve;
          int32_t t = classify_value(s, i, end, &vs, &ve);
          if (t == 0) break;
          trow[p] = (int8_t)t;
          vrow[p] = vs;
          erow[p] = ve;
          matched = true;
          found++;
          // value consumed by classification: resume after it
          i = (t == 1) ? ve + 1 : ve;
          break;
        }
      }
      if (!matched) i = skip_value(s, i, end);
      i = skip_ws(s, i, end);
      if (i < end && s[i] == ',') i++;
      if (found == k) break;  // everything located
    }
}

int64_t rp_find_multi(const uint8_t* joined, const int64_t* offsets,
                      const int32_t* sizes, int64_t n,
                      const char* paths_blob, const int32_t* path_off,
                      const int32_t* path_lens, int32_t k, int8_t* types,
                      int64_t* vs_arr, int64_t* ve_arr) {
  for (int64_t r = 0; r < n; r++) {
    find_in_record(joined + offsets[r], (int64_t)sizes[r], paths_blob,
                   path_off, path_lens, k, types + r * k, vs_arr + r * k,
                   ve_arr + r * k);
  }
  return n;
}

// Fused explode + find: parse every batch's record framing AND walk each
// record's JSON value for the k paths in the SAME pass, while the record
// bytes are cache-hot — the engine's two hottest stages in one crossing
// and one memory traversal. Outputs match rp_parse_many (val_off/val_len,
// absolute into joined) plus rp_find_multi's span tables. Returns records
// parsed (== sum(counts) on success).
int64_t rp_explode_find(const uint8_t* joined, const int64_t* payload_off,
                        const int32_t* payload_len, const int32_t* counts,
                        int32_t n_batches, const char* paths_blob,
                        const int32_t* path_off, const int32_t* path_lens,
                        int32_t k, int64_t* val_off, int32_t* val_len,
                        int8_t* types, int64_t* vs_arr, int64_t* ve_arr) {
  int64_t r = 0;
  for (int32_t b = 0; b < n_batches; b++) {
    const uint8_t* p = joined + payload_off[b];
    const uint8_t* end = p + payload_len[b];
    for (int32_t i = 0; i < counts[b]; i++, r++) {
      const uint8_t* value;
      int64_t vlen;
      if (!parse_one_record(&p, end, &value, &vlen)) return r;
      val_off[r] = value - joined;
      if (vlen < 0) {
        val_len[r] = -1;
        std::memset(types + r * k, 0, (size_t)k);
      } else {
        val_len[r] = (int32_t)vlen;
        find_in_record(value, vlen, paths_blob, path_off, path_lens, k,
                       types + r * k, vs_arr + r * k, ve_arr + r * k);
      }
    }
  }
  return r;
}

// One record's projection row off its span-table row — THE shared body of
// rp_project_rows and the fused rp_extract_cols2, so the packed layout
// and ok-mask rules cannot diverge between the staged and fused ladders.
// Byte-layout parity with ColumnarPlan.assemble_rows: int/float = 4 bytes
// LE; str = LE16 clipped length + w bytes zero-padded. *ok mirrors
// extract_projection's per-kind validity (int: PRESENT|NUMBER|INT_EXACT
// and |v| <= 999999999; float: PRESENT|NUMBER; str: present and fits w).
// descs: per field {kind(0 int, 1 float, 2 str), span col, w, out off}.
static inline void project_one_row(const uint8_t* rec, const int8_t* trow,
                                   const int64_t* vrow, const int64_t* erow,
                                   const int32_t* descs, int32_t n_fields,
                                   int32_t r_out, uint8_t* row, uint8_t* ok) {
  std::memset(row, 0, (size_t)r_out);
  uint8_t okr = 1;
  for (int32_t f = 0; f < n_fields; f++) {
    const int32_t* d = descs + f * 4;
    int32_t kind = d[0], col = d[1], w = d[2], off = d[3];
    if (kind == 2) {  // str
      if (trow[col] != 1) {
        okr = 0;  // missing / non-string: zeroed slot, record dropped
        continue;
      }
      int64_t vlen = erow[col] - vrow[col];
      if (vlen < 0) vlen = 0;  // unterminated: empty-but-present
      if (vlen > w) okr = 0;
      int32_t slen = (int32_t)(vlen < w ? vlen : w);
      row[off] = (uint8_t)(slen & 0xFF);
      row[off + 1] = (uint8_t)((slen >> 8) & 0xFF);
      std::memcpy(row + off + 2, rec + vrow[col], (size_t)slen);
    } else {
      float f32;
      int32_t i32;
      uint8_t fl;
      num_from_span(rec, trow[col], vrow[col], erow[col], &f32, &i32, &fl);
      if (kind == 0) {  // int
        const uint8_t need = RP_F_PRESENT | RP_F_NUMBER | RP_F_INT_EXACT;
        if ((fl & need) != need || i32 > 999999999 || i32 < -999999999)
          okr = 0;
        std::memcpy(row + off, &i32, 4);
      } else {  // float
        const uint8_t need = RP_F_PRESENT | RP_F_NUMBER;
        if ((fl & need) != need) okr = 0;
        std::memcpy(row + off, &f32, 4);
      }
    }
  }
  *ok = okr;
}

// Fused projection: gather every Int/Float/Str projection field straight
// from the span tables into the PACKED output rows in one pass per record
// (replaces k gather_* crossings + the numpy row assembly). One shared
// per-record body with the fused extractor: project_one_row.
int64_t rp_project_rows(const uint8_t* joined, const int64_t* offsets,
                        int64_t n, const int8_t* types, const int64_t* vs,
                        const int64_t* ve, int32_t k, const int32_t* descs,
                        int32_t n_fields, int32_t r_out, uint8_t* rows,
                        uint8_t* ok) {
  for (int64_t r = 0; r < n; r++) {
    project_one_row(joined + offsets[r], types + r * k, vs + r * k,
                    ve + r * k, descs, n_fields, r_out,
                    rows + r * (int64_t)r_out, ok + r);
  }
  return n;
}

// Gather a string column from a precomputed span table column.
void rp_gather_str(const uint8_t* joined, const int64_t* offsets, int64_t n,
                   const int8_t* types, const int64_t* vs, const int64_t* ve,
                   int32_t w, uint8_t* out_bytes, int32_t* out_vlen) {
  for (int64_t i = 0; i < n; i++) {
    uint8_t* dst = out_bytes + i * (int64_t)w;
    std::memset(dst, 0, (size_t)w);
    if (types[i] != 1) {
      out_vlen[i] = -1;
      continue;
    }
    int64_t vlen = ve[i] - vs[i];
    if (vlen < 0) vlen = 0;  // unterminated string: empty-but-present
    if (vlen > (1 << 30)) vlen = 1 << 30;
    out_vlen[i] = (int32_t)vlen;
    int64_t cp = vlen < w ? vlen : w;
    std::memcpy(dst, joined + offsets[i] + vs[i], (size_t)cp);
  }
}

// Gather a numeric column from a precomputed span table column.
void rp_gather_num(const uint8_t* joined, const int64_t* offsets, int64_t n,
                   const int8_t* types, const int64_t* vs, const int64_t* ve,
                   float* out_f32, int32_t* out_i32, uint8_t* out_flags) {
  for (int64_t i = 0; i < n; i++) {
    num_from_span(joined + offsets[i], types[i], vs[i], ve[i], out_f32 + i,
                  out_i32 + i, out_flags + i);
  }
}

// Extract a numeric/bool/null field as (f32, i32, flags) per record.
// Numbers parse as double then narrow: INT_EXACT when integral and within
// int32. Strings/objects/arrays set PRESENT only. Missing -> flags 0.
int64_t rp_extract_num(const uint8_t* joined, const int64_t* offsets,
                       const int32_t* sizes, int64_t n, const char* path,
                       int32_t path_len, float* out_f32, int32_t* out_i32,
                       uint8_t* out_flags) {
  int64_t hits = 0;
  for (int64_t i = 0; i < n; i++) {
    out_f32[i] = 0.0f;
    out_i32[i] = 0;
    out_flags[i] = 0;
    int32_t sz = sizes[i];
    if (sz <= 0) continue;
    int64_t vs, ve;
    int32_t t = rp_json_find(joined + offsets[i], sz, path, path_len, &vs, &ve);
    if (t == 0) continue;
    hits++;
    num_from_span(joined + offsets[i], t, vs, ve, out_f32 + i, out_i32 + i,
                  out_flags + i);
  }
  return hits;
}

// ------------------------------------------------------------- structural
// Two-stage structural-index parse (Langdale & Lemire, "Parsing Gigabytes
// of JSON per Second"), adapted to the engine's record shape. Stage 1 is a
// vectorized character-class scan over each record's JSON value producing
// two bitmaps (bit i = value byte i): unescaped quotes, and structural
// operators ({}[]:,) OUTSIDE strings — escape runs and string interiors
// are computed branch-free with carried word ops, and the scan is seeded
// fresh per record so inter-record framing bytes can never contaminate
// the masks. Stage 2 (find2_in_record) is byte-for-byte the scalar
// find_in_record control flow, except string skips jump straight to the
// closing-quote bit and container skips walk the operator bitmap instead
// of re-scanning bytes. rp_explode_find stays exported as the parity
// oracle and fallback (tests/test_structural_parse.py pins the matrix).

static inline uint64_t bb_eq(uint64_t x, uint64_t pat) {
  // 0x80 in each byte of x equal to the broadcast byte `pat`
  uint64_t t = x ^ pat;
  return (t - 0x0101010101010101ULL) & ~t & 0x8080808080808080ULL;
}

static inline uint64_t bb_pack(uint64_t msbs) {
  // gather the 8 byte-MSBs into the low 8 bits (movemask emulation)
  return (msbs * 0x0002040810204081ULL) >> 56;
}

#define RP_BCAST(c) ((uint64_t)0x0101010101010101ULL * (uint8_t)(c))

// Stage-1 eager classification covers ONLY quote + backslash — exactly
// what the escape and in-string masks need, so the eager scan costs two
// byte-compares per 16 bytes (memchr-class throughput). The six operator
// characters are classified LAZILY per word, only when a container skip
// actually walks them (classify_op_word below) — string-heavy records
// (the bench shape: one ~1KB string value per record) never pay for them.

#if HAVE_X86_64
static void classify2_sse2(const uint8_t* p, uint64_t* quote,
                           uint64_t* bslash) {
  uint64_t q = 0, b = 0;
  const __m128i vq = _mm_set1_epi8('"');
  const __m128i vb = _mm_set1_epi8('\\');
  for (int i = 0; i < 4; i++) {
    __m128i v = _mm_loadu_si128((const __m128i*)(p + 16 * i));
    q |= (uint64_t)(uint32_t)_mm_movemask_epi8(_mm_cmpeq_epi8(v, vq))
         << (16 * i);
    b |= (uint64_t)(uint32_t)_mm_movemask_epi8(_mm_cmpeq_epi8(v, vb))
         << (16 * i);
  }
  *quote = q;
  *bslash = b;
}

#else
static void classify2_swar(const uint8_t* p, uint64_t* quote,
                           uint64_t* bslash) {
  uint64_t q = 0, b = 0;
  for (int i = 0; i < 8; i++) {
    uint64_t x;
    std::memcpy(&x, p + 8 * i, 8);
    q |= bb_pack(bb_eq(x, RP_BCAST('"'))) << (8 * i);
    b |= bb_pack(bb_eq(x, RP_BCAST('\\'))) << (8 * i);
  }
  *quote = q;
  *bslash = b;
}
#endif

// Operator bitmap for ONE 64-byte word of the value, classified on demand
// ({}[]:, — container skips are the only consumer). Tail words pad with
// zeros so the classifier never reads past the value span.
static uint64_t classify_op_word(const uint8_t* s, int64_t w, int64_t end) {
  const uint8_t* p = s + (w << 6);
  uint8_t buf[64];
  if ((w << 6) + 64 > end) {
    std::memset(buf, 0, 64);
    std::memcpy(buf, p, (size_t)(end - (w << 6)));
    p = buf;
  }
#if HAVE_X86_64
  uint64_t o = 0;
  const __m128i c1 = _mm_set1_epi8('{'), c2 = _mm_set1_epi8('}');
  const __m128i c3 = _mm_set1_epi8('['), c4 = _mm_set1_epi8(']');
  const __m128i c5 = _mm_set1_epi8(':'), c6 = _mm_set1_epi8(',');
  for (int i = 0; i < 4; i++) {
    __m128i v = _mm_loadu_si128((const __m128i*)(p + 16 * i));
    __m128i m = _mm_or_si128(
        _mm_or_si128(_mm_cmpeq_epi8(v, c1), _mm_cmpeq_epi8(v, c2)),
        _mm_or_si128(
            _mm_or_si128(_mm_cmpeq_epi8(v, c3), _mm_cmpeq_epi8(v, c4)),
            _mm_or_si128(_mm_cmpeq_epi8(v, c5), _mm_cmpeq_epi8(v, c6))));
    o |= (uint64_t)(uint32_t)_mm_movemask_epi8(m) << (16 * i);
  }
  return o;
#else
  uint64_t o = 0;
  for (int i = 0; i < 8; i++) {
    uint64_t x;
    std::memcpy(&x, p + 8 * i, 8);
    uint64_t m = bb_eq(x, RP_BCAST('{')) | bb_eq(x, RP_BCAST('}')) |
                 bb_eq(x, RP_BCAST('[')) | bb_eq(x, RP_BCAST(']')) |
                 bb_eq(x, RP_BCAST(':')) | bb_eq(x, RP_BCAST(','));
    o |= bb_pack(m) << (8 * i);
  }
  return o;
#endif
}

static inline uint64_t prefix_xor64(uint64_t x) {
  x ^= x << 1;
  x ^= x << 2;
  x ^= x << 4;
  x ^= x << 8;
  x ^= x << 16;
  x ^= x << 32;
  return x;
}

// Characters escaped by an odd-length backslash run (simdjson's
// find_escaped_branchless); *prev carries runs across word boundaries.
// Equivalent to the scalar backward odd-count at every quote because a
// run can never cross an opening quote (the quote byte breaks it).
static inline uint64_t find_escaped(uint64_t backslash, uint64_t* prev) {
  backslash &= ~*prev;
  uint64_t follows_escape = (backslash << 1) | *prev;
  const uint64_t even_bits = 0x5555555555555555ULL;
  uint64_t odd_starts = backslash & ~even_bits & ~follows_escape;
  uint64_t seq = odd_starts + backslash;
  *prev = seq < backslash;  // carry out: an odd run reaches the word end
  uint64_t invert = seq << 1;
  return (even_bits ^ invert) & follows_escape;
}

// Stage 1 over one record value: fill qbits (unescaped quotes) and sbits
// (the string-interior mask: 1 from each opening quote through the byte
// before its closing quote). Carries reset here, per record — framing
// bytes between records can never contaminate the masks. The body is a
// macro so each dispatch variant inlines its classifier (an indirect call
// per 64-byte block costs more than the classification itself), and words
// with no quote and no backslash — the string-body common case — take a
// two-store fast path: escape state decays (the pending escape consumed a
// non-quote byte) and the string mask holds.
#define RP_BUILD_STRUCTURAL_BODY(CLASSIFY2)                                  \
  uint64_t prev_escaped = 0;                                                 \
  uint64_t in_string = 0; /* 0 or ~0: string-interior carry */               \
  int64_t nwords = (len + 63) >> 6;                                          \
  for (int64_t w = 0; w < nwords; w++) {                                     \
    const uint8_t* p = s + (w << 6);                                         \
    uint64_t q, b;                                                           \
    if ((w << 6) + 64 <= len) {                                              \
      CLASSIFY2(p, &q, &b);                                                  \
    } else {                                                                 \
      /* tail block: copy-pad to 64 zero bytes — never read past the     */  \
      /* value span (the next record's framing bytes, or the blob end)   */  \
      uint8_t buf[64];                                                       \
      std::memset(buf, 0, 64);                                               \
      std::memcpy(buf, p, (size_t)(len - (w << 6)));                         \
      CLASSIFY2(buf, &q, &b);                                                \
    }                                                                        \
    if ((q | b) == 0) {                                                      \
      prev_escaped = 0;                                                      \
      qbits[w] = 0;                                                          \
      sbits[w] = in_string;                                                  \
      continue;                                                              \
    }                                                                        \
    uint64_t esc = find_escaped(b, &prev_escaped);                           \
    q &= ~esc;                                                               \
    /* inclusive prefix XOR of quote bits: 1 from each opening quote    */   \
    /* through the byte before its closing quote — exactly where an     */   \
    /* operator byte is string content, not structure                   */   \
    uint64_t S = prefix_xor64(q) ^ in_string;                                \
    in_string = (uint64_t)(-(int64_t)(S >> 63));                             \
    qbits[w] = q;                                                            \
    sbits[w] = S;                                                            \
  }

#if HAVE_X86_64
static void build_structural_sse2(const uint8_t* s, int64_t len,
                                  uint64_t* qbits, uint64_t* sbits) {
  RP_BUILD_STRUCTURAL_BODY(classify2_sse2)
}
__attribute__((target("avx2")))
static void build_structural_avx2(const uint8_t* s, int64_t len,
                                  uint64_t* qbits, uint64_t* sbits) {
  // hand-specialized: vptest answers "any quote/backslash in these 64
  // bytes" straight from the compare vectors, so the dominant string-body
  // words never pay the movemask+shift assembly of the generic path
  const __m256i vq = _mm256_set1_epi8('"');
  const __m256i vb = _mm256_set1_epi8('\\');
  uint64_t prev_escaped = 0;
  uint64_t in_string = 0;
  int64_t nwords = (len + 63) >> 6;
  for (int64_t w = 0; w < nwords; w++) {
    const uint8_t* p = s + (w << 6);
    uint8_t buf[64];
    if ((w << 6) + 64 > len) {
      std::memset(buf, 0, 64);
      std::memcpy(buf, p, (size_t)(len - (w << 6)));
      p = buf;
    }
    __m256i v0 = _mm256_loadu_si256((const __m256i*)p);
    __m256i v1 = _mm256_loadu_si256((const __m256i*)(p + 32));
    __m256i q0 = _mm256_cmpeq_epi8(v0, vq), q1 = _mm256_cmpeq_epi8(v1, vq);
    __m256i b0 = _mm256_cmpeq_epi8(v0, vb), b1 = _mm256_cmpeq_epi8(v1, vb);
    __m256i any = _mm256_or_si256(_mm256_or_si256(q0, q1),
                                  _mm256_or_si256(b0, b1));
    if (_mm256_testz_si256(any, any)) {
      prev_escaped = 0;
      qbits[w] = 0;
      sbits[w] = in_string;
      continue;
    }
    uint64_t q = (uint64_t)(uint32_t)_mm256_movemask_epi8(q0) |
                 ((uint64_t)(uint32_t)_mm256_movemask_epi8(q1) << 32);
    uint64_t b = (uint64_t)(uint32_t)_mm256_movemask_epi8(b0) |
                 ((uint64_t)(uint32_t)_mm256_movemask_epi8(b1) << 32);
    uint64_t esc = find_escaped(b, &prev_escaped);
    q &= ~esc;
    uint64_t S = prefix_xor64(q) ^ in_string;
    in_string = (uint64_t)(-(int64_t)(S >> 63));
    qbits[w] = q;
    sbits[w] = S;
  }
}
typedef void (*build_structural_fn)(const uint8_t*, int64_t, uint64_t*,
                                    uint64_t*);
static build_structural_fn build_structural_resolve() {
  // same runtime-dispatch posture as the CRC path: AVX2 instructions live
  // only behind the cpu check, the .so itself stays baseline-x86_64
  static build_structural_fn impl = nullptr;
  build_structural_fn fn = impl;
  if (!fn) {
    fn = __builtin_cpu_supports("avx2") ? build_structural_avx2
                                        : build_structural_sse2;
    impl = fn;
  }
  return fn;
}
static void build_structural(const uint8_t* s, int64_t len, uint64_t* qbits,
                             uint64_t* sbits) {
  build_structural_resolve()(s, len, qbits, sbits);
}
#else
static void build_structural(const uint8_t* s, int64_t len, uint64_t* qbits,
                             uint64_t* sbits) {
  RP_BUILD_STRUCTURAL_BODY(classify2_swar)
}
#endif

static inline int64_t next_set_bit(const uint64_t* words, int64_t len,
                                   int64_t from) {
  if (from >= len) return -1;
  int64_t w = from >> 6;
  uint64_t cur = words[w] & (~0ULL << (from & 63));
  for (;;) {
    if (cur) return (w << 6) + __builtin_ctzll(cur);
    if (((++w) << 6) >= len) return -1;
    cur = words[w];
  }
}

// skip_string twin over the quote bitmap: i at the opening quote. The next
// quote BIT is the closing quote by construction (escaped quotes are
// masked out of qbits; operators between them are irrelevant here).
static inline int64_t skip_string_idx(int64_t i, int64_t end,
                                      const uint64_t* qbits) {
  int64_t close = next_set_bit(qbits, end, i + 1);
  return close < 0 ? end : close + 1;
}

// skip_value twin: containers walk lazily classified operator words
// (masked by the stored string-interior bits), strings jump via the quote
// bitmap, primitives byte-scan exactly like the scalar walker (their
// tokens are a few bytes and the scalar stop set must be honored
// byte-for-byte).
static int64_t skip_value_idx(const uint8_t* s, int64_t i, int64_t end,
                              const uint64_t* qbits, const uint64_t* sbits) {
  i = skip_ws(s, i, end);
  if (i >= end) return end;
  uint8_t c = s[i];
  if (c == '"') return skip_string_idx(i, end, qbits);
  if (c == '{' || c == '[') {
    int64_t depth = 0;
    int64_t nwords = (end + 63) >> 6;
    uint64_t first_mask = ~0ULL << (i & 63);
    for (int64_t w = i >> 6; w < nwords; w++) {
      uint64_t ow = classify_op_word(s, w, end) & ~sbits[w] & first_mask;
      first_mask = ~0ULL;
      while (ow) {
        int64_t p = (w << 6) + __builtin_ctzll(ow);
        ow &= ow - 1;
        uint8_t pc = s[p];
        if (pc == '{' || pc == '[') {
          depth++;
        } else if (pc == '}' || pc == ']') {
          depth--;
          if (depth == 0) return p + 1;
        }
        // ':' and ',' are structural but depth-neutral
      }
    }
    return end;
  }
  while (i < end && c != ',' && c != '}' && c != ']' && c != ' ' &&
         c != '\t' && c != '\n' && c != '\r') {
    i++;
    if (i < end) c = s[i];
  }
  return i;
}

// classify_value twin; token typing shares the scalar rules verbatim.
static int32_t classify_value_idx(const uint8_t* s, int64_t i, int64_t end,
                                  const uint64_t* qbits,
                                  const uint64_t* sbits, int64_t* vs,
                                  int64_t* ve) {
  if (i >= end) return 0;
  uint8_t c = s[i];
  if (c == '"') {
    int64_t j = skip_string_idx(i, end, qbits);
    *vs = i + 1;
    *ve = j - 1;
    return 1;
  }
  if (c == '{') {
    *vs = i;
    *ve = skip_value_idx(s, i, end, qbits, sbits);
    return 6;
  }
  if (c == '[') {
    *vs = i;
    *ve = skip_value_idx(s, i, end, qbits, sbits);
    return 7;
  }
  int64_t j = skip_value_idx(s, i, end, qbits, sbits);
  *vs = i;
  *ve = j;
  int64_t tl = j - i;
  if (tl == 4 && std::memcmp(s + i, "true", 4) == 0) return 3;
  if (tl == 5 && std::memcmp(s + i, "false", 5) == 0) return 4;
  if (tl == 4 && std::memcmp(s + i, "null", 4) == 0) return 5;
  return 2;
}

// Stage 2: find_in_record with the three skip primitives swapped for their
// structural-index twins. The control flow is line-for-line the scalar
// walker's, so the two walks cannot diverge on ANY input — well-formed or
// malformed — except through the skip primitives, whose equivalence the
// parity suite pins (escaped quotes, backslash runs, unterminated
// strings, truncated records).
static void find2_in_record(const uint8_t* s, int64_t end,
                            const uint64_t* qbits, const uint64_t* sbits,
                            const char* paths_blob, const int32_t* path_off,
                            const int32_t* path_lens, int32_t k,
                            int8_t* trow, int64_t* vrow, int64_t* erow) {
  std::memset(trow, 0, (size_t)k);
  if (end <= 0) return;
  int64_t i = skip_ws(s, 0, end);
  if (i >= end || s[i] != '{') return;
  i++;
  int32_t found = 0;
  for (;;) {
    i = skip_ws(s, i, end);
    if (i >= end || s[i] == '}') break;
    if (s[i] != '"') break;  // malformed
    int64_t kstart = i + 1;
    i = skip_string_idx(i, end, qbits);
    int64_t kend = i - 1;
    i = skip_ws(s, i, end);
    if (i >= end || s[i] != ':') break;
    i++;
    i = skip_ws(s, i, end);
    int64_t klen = kend - kstart;
    bool matched = false;
    for (int32_t p = 0; p < k; p++) {
      if (trow[p] != 0) continue;  // first occurrence wins
      if (klen == path_lens[p] &&
          std::memcmp(s + kstart, paths_blob + path_off[p],
                      (size_t)path_lens[p]) == 0) {
        int64_t vs, ve;
        int32_t t = classify_value_idx(s, i, end, qbits, sbits, &vs, &ve);
        if (t == 0) break;
        trow[p] = (int8_t)t;
        vrow[p] = vs;
        erow[p] = ve;
        matched = true;
        found++;
        i = (t == 1) ? ve + 1 : ve;
        break;
      }
    }
    if (!matched) i = skip_value_idx(s, i, end, qbits, sbits);
    i = skip_ws(s, i, end);
    if (i < end && s[i] == ',') i++;
    if (found == k) break;  // everything located
  }
}

// Structural-index fused parse: the launch's payload bytes cross the
// native boundary ONCE, as a table of per-batch source pointers — no
// Python-side b"".join. When `joined_out` is given (passthrough plans,
// whose zero-copy harvest gathers output bytes from the blob) each
// payload is memcpy'd in first and parsed cache-hot from the copy; when
// NULL (projection plans — nothing downstream ever reads the raw bytes
// again) records parse straight from the source buffers and the blob is
// never built. val_off is absolute into the (possibly virtual)
// concatenation either way, so the index tables are identical to
// rp_explode_find's. Returns records parsed (== sum(counts) on success),
// or -1 on scratch allocation failure.
int64_t rp_explode_find2(const uint8_t* const* payloads,
                         const int32_t* payload_len, const int32_t* counts,
                         int32_t n_batches, uint8_t* joined_out,
                         const char* paths_blob, const int32_t* path_off,
                         const int32_t* path_lens, int32_t k,
                         int64_t* val_off, int32_t* val_len, int8_t* types,
                         int64_t* vs_arr, int64_t* ve_arr) {
  // one scratch bitmap pair sized to the largest payload (a record value
  // can never outgrow its batch payload), reused cache-hot per record
  int64_t max_words = 1;
  for (int32_t b = 0; b < n_batches; b++) {
    int64_t w = ((int64_t)payload_len[b] + 63) >> 6;
    if (w > max_words) max_words = w;
  }
  uint64_t* qbits = (uint64_t*)std::malloc((size_t)max_words * 8);
  uint64_t* sbits = (uint64_t*)std::malloc((size_t)max_words * 8);
  if (!qbits || !sbits) {
    std::free(qbits);
    std::free(sbits);
    return -1;
  }
  int64_t r = 0;
  int64_t base = 0;
  for (int32_t b = 0; b < n_batches; b++) {
    const uint8_t* src = payloads[b];
    if (joined_out) {
      std::memcpy(joined_out + base, src, (size_t)payload_len[b]);
      src = joined_out + base;  // parse the copy while it is cache-hot
    }
    const uint8_t* p = src;
    const uint8_t* end = p + payload_len[b];
    for (int32_t i = 0; i < counts[b]; i++, r++) {
      const uint8_t* value;
      int64_t vlen;
      if (!parse_one_record(&p, end, &value, &vlen)) {
        std::free(qbits);
        std::free(sbits);
        return r;
      }
      val_off[r] = base + (value - src);
      if (vlen < 0) {
        val_len[r] = -1;
        std::memset(types + r * k, 0, (size_t)k);
      } else {
        val_len[r] = (int32_t)vlen;
        build_structural(value, vlen, qbits, sbits);
        find2_in_record(value, vlen, qbits, sbits, paths_blob, path_off,
                        path_lens, k, types + r * k, vs_arr + r * k,
                        ve_arr + r * k);
      }
    }
    base += payload_len[b];
  }
  std::free(qbits);
  std::free(sbits);
  return r;
}

// Fused extraction: every predicate input column AND (optionally) the
// packed projection rows gathered from the span tables in ONE
// record-major pass — replaces the per-column gather crossings, the
// separate rp_project_rows crossing and the numpy pad concatenations.
// Record bytes resolve against the per-batch source buffers (the same
// pointer table rp_explode_find2 consumed), so no joined blob is needed.
// pred_descs is [n_pred, 4] int32 {kind: 0 num, 1 str, 2 exists; span
// col; w; unused}; pred_ptrs holds the outputs in desc order with
// per-kind arity num=3 (f32, i32, flags), str=2 (bytes [n_pad, w], vlen
// i32), exists=1 (u8); rows [n, n_pad) get the staged extractors' exact
// pad semantics (zeros; str vlen -1). proj_descs/proj_rows/proj_ok (may
// be empty/NULL) follow rp_project_rows' desc layout and byte semantics.
void rp_extract_cols2(const uint8_t* const* payloads,
                      const int32_t* payload_len, const int32_t* counts,
                      int32_t n_batches, const int64_t* val_off,
                      const int32_t* val_len, const int8_t* types,
                      const int64_t* vs, const int64_t* ve, int32_t k,
                      const int32_t* pred_descs, int32_t n_pred,
                      void** pred_ptrs, int64_t n_pad,
                      const int32_t* proj_descs, int32_t n_proj,
                      int32_t r_out, uint8_t* proj_rows, uint8_t* proj_ok) {
  int64_t r = 0;
  int64_t base = 0;
  for (int32_t b = 0; b < n_batches; b++) {
    const uint8_t* buf = payloads[b];
    for (int32_t i = 0; i < counts[b]; i++, r++) {
      // null values (val_len -1) keep rec at the batch buffer: their
      // types row is all 0, so every extractor below emits "absent"
      // without dereferencing the span
      const uint8_t* rec = buf + (val_off[r] - base);
      const int8_t* trow = types + r * k;
      const int64_t* vrow = vs + r * k;
      const int64_t* erow = ve + r * k;
      int32_t pi = 0;
      for (int32_t d = 0; d < n_pred; d++) {
        const int32_t* de = pred_descs + d * 4;
        int32_t kind = de[0], col = de[1], w = de[2];
        if (kind == 0) {  // num: (f32, i32, flags) — rp_gather_num parity
          num_from_span(rec, trow[col], vrow[col], erow[col],
                        (float*)pred_ptrs[pi] + r,
                        (int32_t*)pred_ptrs[pi + 1] + r,
                        (uint8_t*)pred_ptrs[pi + 2] + r);
          pi += 3;
        } else if (kind == 1) {  // str — rp_gather_str parity
          uint8_t* dst = (uint8_t*)pred_ptrs[pi] + r * (int64_t)w;
          int32_t* out_vlen = (int32_t*)pred_ptrs[pi + 1];
          std::memset(dst, 0, (size_t)w);
          if (trow[col] != 1) {
            out_vlen[r] = -1;
          } else {
            int64_t vlen = erow[col] - vrow[col];
            if (vlen < 0) vlen = 0;  // unterminated: empty-but-present
            if (vlen > (1 << 30)) vlen = 1 << 30;
            out_vlen[r] = (int32_t)vlen;
            int64_t cp = vlen < w ? vlen : w;
            std::memcpy(dst, rec + vrow[col], (size_t)cp);
          }
          pi += 2;
        } else {  // exists
          ((uint8_t*)pred_ptrs[pi])[r] = trow[col] != 0;
          pi += 1;
        }
      }
      if (n_proj > 0) {
        project_one_row(rec, trow, vrow, erow, proj_descs, n_proj, r_out,
                        proj_rows + r * (int64_t)r_out, proj_ok + r);
      }
    }
    base += payload_len[b];
  }
  if (n_pad > r) {
    int64_t n = r;
    int64_t pad = n_pad - n;
    int32_t pi = 0;
    for (int32_t d = 0; d < n_pred; d++) {
      const int32_t* de = pred_descs + d * 4;
      int32_t kind = de[0], w = de[2];
      if (kind == 0) {
        std::memset((float*)pred_ptrs[pi] + n, 0, (size_t)pad * 4);
        std::memset((int32_t*)pred_ptrs[pi + 1] + n, 0, (size_t)pad * 4);
        std::memset((uint8_t*)pred_ptrs[pi + 2] + n, 0, (size_t)pad);
        pi += 3;
      } else if (kind == 1) {
        std::memset((uint8_t*)pred_ptrs[pi] + n * (int64_t)w, 0,
                    (size_t)(pad * w));
        int32_t* vl = (int32_t*)pred_ptrs[pi + 1];
        for (int64_t j = n; j < n_pad; j++) vl[j] = -1;
        pi += 2;
      } else {
        std::memset((uint8_t*)pred_ptrs[pi] + n, 0, (size_t)pad);
        pi += 1;
      }
    }
  }
}

// Presence-only column (exists()): 1 when the path resolves to any value.
int64_t rp_extract_exists(const uint8_t* joined, const int64_t* offsets,
                          const int32_t* sizes, int64_t n, const char* path,
                          int32_t path_len, uint8_t* out) {
  int64_t hits = 0;
  for (int64_t i = 0; i < n; i++) {
    out[i] = 0;
    int32_t sz = sizes[i];
    if (sz <= 0) continue;
    int64_t vs, ve;
    if (rp_json_find(joined + offsets[i], sz, path, path_len, &vs, &ve) != 0) {
      out[i] = 1;
      hits++;
    }
  }
  return hits;
}

}  // extern "C"
