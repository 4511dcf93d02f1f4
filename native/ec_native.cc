// Host-CPU GF(2^8) region codec + crc32c — the native runtime kernels.
//
// Role: the reference accelerates its erasure-code hot loop with vendored
// SIMD libraries (isa-l's ec_encode_data, reference
// src/erasure-code/isa/ErasureCodeIsa.cc:129; jerasure/gf-complete SSE
// region ops) and its checksums with runtime-dispatched crc32c kernels
// (reference src/common/crc32c.cc:17).  This file provides the same two
// capabilities for the TPU framework's host side, written from the standard
// published techniques (split-nibble PSHUFB multiply tables; CRC32C via the
// SSE4.2 instruction, three interleaved chains merged by shift tables, with
// a table-driven fallback) — no reference code.
//
// It is used as (a) the honest host-CPU baseline the TPU path is measured
// against, and (b) the host verify/fallback path when no accelerator is up.
//
// Exposed C ABI (consumed via ctypes from ceph_tpu.native):
//   gf256_encode(M, m, k, tables, data, out, n)   out = M @ data over GF(2^8)
//   gf256_region_xor(src, dst, n)                 dst ^= src
//   crc32c(crc, data, n) -> uint32_t              Castagnoli CRC
//   crc32c_blocks(data, nblocks, bs, seed, out)   per-block CRCs (Checksummer)
//   ec_native_crc32c_impl() -> "hw3" | "sw"       the kernel crc32c() runs
//   ec_native_crc32c_sw(crc, data, n)             the table kernel, always
//   frame_pack(...)                               msgr2 frame codec: preamble,
//                                                 segments and crcs, one blob
//   frame_crcs(...)                               preamble and crcs alone: the
//                                                 segments go by reference
//   frame_verify_body(...)                        a received body's crcs
//   rxw_start/stop/submit/cancel/reap/...         the messenger's receive
//                                                 worker (Linux; at the end)
//   ec_native_have_avx2() / ec_native_have_sse42()

#include <cstdint>
#include <cstddef>
#include <cstring>

#if defined(__x86_64__)
#include <immintrin.h>
#endif

namespace {

// ---------------------------------------------------------------------------
// GF(2^8) region multiply-accumulate: dst ^= c * src
// Split-nibble tables: c*x == TLO[x & 15] ^ THI[x >> 4]  (linearity over GF2).
// `tab` points at 32 bytes: TLO[0..15] then THI[0..15] for this coefficient.
// ---------------------------------------------------------------------------

void mul_xor_scalar(const uint8_t* tab, const uint8_t* src, uint8_t* dst,
                    size_t n) {
  const uint8_t* tlo = tab;
  const uint8_t* thi = tab + 16;
  for (size_t i = 0; i < n; i++)
    dst[i] ^= (uint8_t)(tlo[src[i] & 15] ^ thi[src[i] >> 4]);
}

void xor_scalar(const uint8_t* src, uint8_t* dst, size_t n) {
  size_t i = 0;
  for (; i + 8 <= n; i += 8) {
    uint64_t a, b;
    memcpy(&a, dst + i, 8);
    memcpy(&b, src + i, 8);
    a ^= b;
    memcpy(dst + i, &a, 8);
  }
  for (; i < n; i++) dst[i] ^= src[i];
}

#if defined(__x86_64__)
__attribute__((target("avx2")))
void mul_xor_avx2(const uint8_t* tab, const uint8_t* src, uint8_t* dst,
                  size_t n) {
  const __m256i lo =
      _mm256_broadcastsi128_si256(_mm_loadu_si128((const __m128i*)tab));
  const __m256i hi =
      _mm256_broadcastsi128_si256(_mm_loadu_si128((const __m128i*)(tab + 16)));
  const __m256i mask = _mm256_set1_epi8(0x0f);
  size_t i = 0;
  for (; i + 32 <= n; i += 32) {
    __m256i s = _mm256_loadu_si256((const __m256i*)(src + i));
    __m256i d = _mm256_loadu_si256((const __m256i*)(dst + i));
    __m256i l = _mm256_shuffle_epi8(lo, _mm256_and_si256(s, mask));
    __m256i h = _mm256_shuffle_epi8(
        hi, _mm256_and_si256(_mm256_srli_epi16(s, 4), mask));
    _mm256_storeu_si256((__m256i*)(dst + i),
                        _mm256_xor_si256(d, _mm256_xor_si256(l, h)));
  }
  if (i < n) mul_xor_scalar(tab, src + i, dst + i, n - i);
}

__attribute__((target("avx2")))
void xor_avx2(const uint8_t* src, uint8_t* dst, size_t n) {
  size_t i = 0;
  for (; i + 32 <= n; i += 32) {
    __m256i s = _mm256_loadu_si256((const __m256i*)(src + i));
    __m256i d = _mm256_loadu_si256((const __m256i*)(dst + i));
    _mm256_storeu_si256((__m256i*)(dst + i), _mm256_xor_si256(d, s));
  }
  if (i < n) xor_scalar(src + i, dst + i, n - i);
}

bool have_avx2() { return __builtin_cpu_supports("avx2"); }
bool have_sse42() { return __builtin_cpu_supports("sse4.2"); }
#else
bool have_avx2() { return false; }
bool have_sse42() { return false; }
#endif

void mul_xor(const uint8_t* tab, const uint8_t* src, uint8_t* dst, size_t n) {
#if defined(__x86_64__)
  if (have_avx2()) { mul_xor_avx2(tab, src, dst, n); return; }
#endif
  mul_xor_scalar(tab, src, dst, n);
}

void region_xor(const uint8_t* src, uint8_t* dst, size_t n) {
#if defined(__x86_64__)
  if (have_avx2()) { xor_avx2(src, dst, n); return; }
#endif
  xor_scalar(src, dst, n);
}

// ---------------------------------------------------------------------------
// crc32c (Castagnoli, poly 0x1EDC6F41 reflected = 0x82F63B78)
// ---------------------------------------------------------------------------

// Slice-by-8 tables, built once: a function-local static is initialised
// under the compiler's guard, so shard threads that race the first call
// all see complete tables.
struct Crc32cTable {
  uint32_t t[8][256];
  Crc32cTable() {
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int j = 0; j < 8; j++)
        c = (c & 1) ? (c >> 1) ^ 0x82F63B78u : (c >> 1);
      t[0][i] = c;
    }
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = t[0][i];
      for (int s = 1; s < 8; s++) {
        c = t[0][c & 0xff] ^ (c >> 8);
        t[s][i] = c;
      }
    }
  }
};

const Crc32cTable& crc32c_table() {
  static const Crc32cTable table;
  return table;
}

uint32_t crc32c_sw(uint32_t crc, const uint8_t* data, size_t n) {
  const uint32_t (*t)[256] = crc32c_table().t;
  // slice-by-8
  while (n >= 8) {
    uint64_t v;
    memcpy(&v, data, 8);
    v ^= crc;
    crc = t[7][v & 0xff] ^ t[6][(v >> 8) & 0xff] ^
          t[5][(v >> 16) & 0xff] ^ t[4][(v >> 24) & 0xff] ^
          t[3][(v >> 32) & 0xff] ^ t[2][(v >> 40) & 0xff] ^
          t[1][(v >> 48) & 0xff] ^ t[0][(v >> 56) & 0xff];
    data += 8;
    n -= 8;
  }
  while (n--) crc = t[0][(crc ^ *data++) & 0xff] ^ (crc >> 8);
  return crc;
}

#if defined(__x86_64__)
// The crc32 instruction has a latency of 3 cycles and a throughput of one
// a cycle, so one dependent chain (8 bytes every 3 cycles) leaves two
// thirds of the unit idle. Three chains over three adjacent blocks fill
// it; they are merged with the operator "append `len` zero bytes", which
// is linear over GF(2) on the raw register (no pre- or post-inversion
// here, ceph's convention):
//   crc(s, A|B|C) = zeros_2L(crc(s, A)) ^ zeros_L(crc(0, B)) ^ crc(0, C).
// The operator for a fixed block length is a 4 x 256 table (the scheme of
// Adler's crc32c.c; upstream's crc32c_intel_fast merges with pclmulqdq).
// Long blocks amortise the two merges over 24 KiB; short blocks serve the
// 4 KiB checksum blocks and the tail of a long run.
constexpr size_t CRC_LONG = 8192;
constexpr size_t CRC_SHORT = 256;

struct Crc32cZeros {
  uint32_t lng[4][256];
  uint32_t shrt[4][256];

  // t[j][b] = the register after `len` zero bytes from b << 8j
  static void build(uint32_t (*t)[256], size_t len) {
    static const uint8_t zeros[CRC_LONG] = {0};
    uint32_t bit[32];
    for (int i = 0; i < 32; i++)
      bit[i] = crc32c_sw(1u << i, zeros, len);
    for (int j = 0; j < 4; j++)
      for (uint32_t b = 0; b < 256; b++) {
        uint32_t c = 0;
        for (int i = 0; i < 8; i++)
          if (b & (1u << i)) c ^= bit[8 * j + i];
        t[j][b] = c;
      }
  }
  Crc32cZeros() {
    build(lng, CRC_LONG);
    build(shrt, CRC_SHORT);
  }
};

const Crc32cZeros& crc32c_zeros() {
  static const Crc32cZeros z;     // once, under the compiler's guard
  return z;
}

inline uint32_t crc32c_shift(const uint32_t (*t)[256], uint32_t c) {
  return t[0][c & 0xff] ^ t[1][(c >> 8) & 0xff] ^ t[2][(c >> 16) & 0xff] ^
         t[3][c >> 24];
}

// Three chains over data[0:3*len], len a multiple of 8.
__attribute__((target("sse4.2")))
inline uint64_t crc32c_hw_x3(uint64_t c0, const uint8_t* data, size_t len,
                             const uint32_t (*t)[256]) {
  uint64_t c1 = 0, c2 = 0;
  const uint8_t* end = data + len;
  do {
    uint64_t a, b, c;
    memcpy(&a, data, 8);
    memcpy(&b, data + len, 8);
    memcpy(&c, data + 2 * len, 8);
    c0 = _mm_crc32_u64(c0, a);
    c1 = _mm_crc32_u64(c1, b);
    c2 = _mm_crc32_u64(c2, c);
    data += 8;
  } while (data < end);
  c0 = crc32c_shift(t, (uint32_t)c0) ^ c1;
  return crc32c_shift(t, (uint32_t)c0) ^ c2;
}

__attribute__((target("sse4.2")))
uint32_t crc32c_hw(uint32_t crc, const uint8_t* data, size_t n) {
  uint64_t c = crc;
  // up to an 8-byte boundary, so the chains load aligned words
  while (n && ((uintptr_t)data & 7)) {
    c = _mm_crc32_u8((uint32_t)c, *data++);
    n--;
  }
  if (n >= 3 * CRC_SHORT) {
    const Crc32cZeros& z = crc32c_zeros();
    while (n >= 3 * CRC_LONG) {
      c = crc32c_hw_x3(c, data, CRC_LONG, z.lng);
      data += 3 * CRC_LONG;
      n -= 3 * CRC_LONG;
    }
    while (n >= 3 * CRC_SHORT) {
      c = crc32c_hw_x3(c, data, CRC_SHORT, z.shrt);
      data += 3 * CRC_SHORT;
      n -= 3 * CRC_SHORT;
    }
  }
  // the tail, under 768 bytes: one chain
  while (n >= 8) {
    uint64_t v;
    memcpy(&v, data, 8);
    c = _mm_crc32_u64(c, v);
    data += 8;
    n -= 8;
  }
  uint32_t c32 = (uint32_t)c;
  while (n--) c32 = _mm_crc32_u8(c32, *data++);
  return c32;
}
#endif

}  // namespace

extern "C" {

int ec_native_have_avx2() { return have_avx2() ? 1 : 0; }
int ec_native_have_sse42() { return have_sse42() ? 1 : 0; }

// out(m,n) = M(m,k) @ data(k,n) over GF(2^8); `tables` is the 256x32 split
// table block: tables[c*32 + v] = mul(c, v) for v<16, mul(c, (v-16)<<4) else.
void gf256_encode(const uint8_t* M, int m, int k, const uint8_t* tables,
                  const uint8_t* data, uint8_t* out, size_t n) {
  for (int i = 0; i < m; i++) {
    uint8_t* dst = out + (size_t)i * n;
    memset(dst, 0, n);
    for (int j = 0; j < k; j++) {
      uint8_t c = M[(size_t)i * k + j];
      if (c == 0) continue;
      const uint8_t* src = data + (size_t)j * n;
      if (c == 1)
        region_xor(src, dst, n);
      else
        mul_xor(tables + (size_t)c * 32, src, dst, n);
    }
  }
}

void gf256_region_xor(const uint8_t* src, uint8_t* dst, size_t n) {
  region_xor(src, dst, n);
}

uint32_t crc32c(uint32_t crc, const uint8_t* data, size_t n) {
#if defined(__x86_64__)
  if (have_sse42()) return crc32c_hw(crc, data, n);
#endif
  return crc32c_sw(crc, data, n);
}

// The kernel crc32c() dispatches to on this host: "hw3" (the crc32
// instruction, three interleaved chains) or "sw" (slice-by-8 tables).
const char* ec_native_crc32c_impl() {
#if defined(__x86_64__)
  if (have_sse42()) return "hw3";
#endif
  return "sw";
}

// The table kernel whatever the host has: what the tests hold the
// dispatched one to.
uint32_t ec_native_crc32c_sw(uint32_t crc, const uint8_t* data, size_t n) {
  return crc32c_sw(crc, data, n);
}

// Per-block CRCs over a contiguous buffer of nblocks x block_size bytes —
// the Checksummer batch shape (reference src/common/Checksummer.h:195-234).
void crc32c_blocks(const uint8_t* data, size_t nblocks, size_t block_size,
                   uint32_t seed, uint32_t* out) {
  for (size_t b = 0; b < nblocks; b++)
    out[b] = crc32c(seed, data + b * block_size, block_size);
}

// ---------------------------------------------------------------------------
// msgr2 frame codec (the hot path of ceph_tpu/msg/frames.py): one C call
// does a frame's codec work — little-endian preamble (magic u16, tag u8,
// seg_count u8, seg_len u32*, preamble crc u32) and each segment's trailing
// crc32c — instead of 2+nseg ctypes round trips and a Python scatter loop
// per frame. frame_pack also copies the segments between them into one wire
// blob (small frames); frame_crcs copies nothing (frames whose segments are
// sent from where they lie). Segments arrive as a FLATTENED part list
// (seg_parts[i] parts belong to segment i) so scatter-gather payloads (the
// sub-op batch envelope's concatenated message datas) need no intermediate
// join: the segment crc chains across its parts. Layout is bit-identical to
// the pure-Python path in frames.py, which stays the fallback when this
// library is unavailable.
// ---------------------------------------------------------------------------

static inline void put_u16le(uint8_t* p, uint16_t v) {
  p[0] = (uint8_t)v;
  p[1] = (uint8_t)(v >> 8);
}

static inline void put_u32le(uint8_t* p, uint32_t v) {
  p[0] = (uint8_t)v;
  p[1] = (uint8_t)(v >> 8);
  p[2] = (uint8_t)(v >> 16);
  p[3] = (uint8_t)(v >> 24);
}

// The preamble of a frame into `out` (8 + 4*nseg bytes): magic, tag,
// segment count, each segment's length summed over its parts, and the crc
// of all that. Returns the end.
static uint8_t* put_preamble(uint32_t magic, uint32_t tag, int nseg,
                             const uint64_t* seg_parts,
                             const uint64_t* part_lens, uint8_t* out) {
  uint8_t* p = out;
  put_u16le(p, (uint16_t)magic);
  p[2] = (uint8_t)tag;
  p[3] = (uint8_t)nseg;
  p += 4;
  size_t part = 0;
  for (int s = 0; s < nseg; s++) {
    uint64_t len = 0;
    for (uint64_t j = 0; j < seg_parts[s]; j++)
      len += part_lens[part + j];
    part += seg_parts[s];
    put_u32le(p, (uint32_t)len);
    p += 4;
  }
  put_u32le(p, crc32c(0, out, (size_t)(p - out)));
  return p + 4;
}

// Pack one frame into `out` (caller sizes it: 4 + 4*nseg + 4 +
// sum(seg_len + 4)). Returns total bytes written.
uint64_t frame_pack(uint32_t magic, uint32_t tag, int nseg,
                    const uint64_t* seg_parts,       // parts per segment
                    const uint8_t* const* parts,     // flattened part ptrs
                    const uint64_t* part_lens,       // flattened part lens
                    uint8_t* out) {
  uint8_t* p = put_preamble(magic, tag, nseg, seg_parts, part_lens, out);
  size_t part = 0;
  for (int s = 0; s < nseg; s++) {
    uint32_t crc = 0;
    for (uint64_t j = 0; j < seg_parts[s]; j++) {
      size_t n = (size_t)part_lens[part + j];
      if (n) {
        memcpy(p, parts[part + j], n);
        crc = crc32c(crc, p, n);
        p += n;
      }
    }
    part += seg_parts[s];
    put_u32le(p, crc);
    p += 4;
  }
  return (uint64_t)(p - out);
}

// The same frame with nothing copied: the preamble and then each segment's
// trailing crc (4 bytes a segment, chained over its parts) into `out`
// (8 + 8*nseg bytes). The caller sends [preamble, parts of segment 0,
// crc 0, ...] from where the parts lie. Returns the bytes written.
uint64_t frame_crcs(uint32_t magic, uint32_t tag, int nseg,
                    const uint64_t* seg_parts,
                    const uint8_t* const* parts,
                    const uint64_t* part_lens,
                    uint8_t* out) {
  uint8_t* p = put_preamble(magic, tag, nseg, seg_parts, part_lens, out);
  size_t part = 0;
  for (int s = 0; s < nseg; s++) {
    uint32_t crc = 0;
    for (uint64_t j = 0; j < seg_parts[s]; j++) {
      size_t n = (size_t)part_lens[part + j];
      if (n) crc = crc32c(crc, parts[part + j], n);
    }
    part += seg_parts[s];
    put_u32le(p, crc);
    p += 4;
  }
  return (uint64_t)(p - out);
}

// Verify a frame body (nseg runs of [seg bytes | crc32c u32]) in one call.
// Returns -1 when every segment checks out, else the index of the first
// segment whose trailing crc mismatches. The caller has already validated
// the preamble (its crc covers the lengths used here).
int frame_verify_body(const uint8_t* body, const uint64_t* seg_lens,
                      int nseg) {
  const uint8_t* p = body;
  for (int s = 0; s < nseg; s++) {
    size_t n = (size_t)seg_lens[s];
    uint32_t want = (uint32_t)p[n] | ((uint32_t)p[n + 1] << 8) |
                    ((uint32_t)p[n + 2] << 16) | ((uint32_t)p[n + 3] << 24);
    if (crc32c(0, p, n) != want) return s;
    p += n + 4;
  }
  return -1;
}

// De-interleave: planes [first, first + count) of `src`, S stripes of n
// chunks of C bytes each, into `dst`, count planes of S * C bytes one
// after another: a shard's chunks of every stripe made contiguous, for
// any run of shards in one call (and so in one stretch without the GIL).
void planes_from_stripes(const uint8_t* src, size_t S, size_t n, size_t C,
                         size_t first, size_t count, uint8_t* dst) {
  for (size_t s = 0; s < S; s++) {
    const uint8_t* row = src + (s * n + first) * C;
    for (size_t p = 0; p < count; p++)
      memcpy(dst + (p * S + s) * C, row + p * C, C);
  }
}

}  // extern "C"

// ---------------------------------------------------------------------------
// The messenger's socket worker (ceph_tpu/msg/rxworker.py; upstream's
// AsyncMessenger Worker, src/msg/async/Stack.h, cut down to what the
// interpreter's lock leaves worth moving): native threads, each with an
// epoll set of the sockets that have a large frame in progress; a
// connection's jobs go to one thread, chosen by its fd. A job is one of two
// kinds.
//   A receive: a dup of the connection's fd, the body's buffer, how much of
// it is already there and the segments' lengths. The thread recvs into the
// unfilled tail, never past the body's end, and chains the segments' crc32c
// over the bytes as they arrive.
//   A send: the connection's send dup (the caller's, for the connection's
// life), the frame's parts where they lie and a header buffer of the job's
// own (the preamble, written at the submit, and four bytes of crc a
// segment). The thread chains each segment's crc32c over its parts a chunk
// ahead of the send, writes it into its slot, and sendmsgs from where the
// bytes lie until the kernel has them all, waiting for EPOLLOUT where the
// socket is full: the bytes on the wire are frame_pack's.
// Either way the thread posts a completion the event loop reaps through one
// call (`rxw_reap`) after a wake-up on an eventfd of its own. The threads
// run no Python and never take the interpreter's lock. A cancel waits until
// the thread has let go of the job's fd and buffers.
// ---------------------------------------------------------------------------

#if defined(__linux__)

#include <atomic>
#include <cerrno>
#include <deque>
#include <mutex>
#include <new>
#include <condition_variable>
#include <thread>
#include <unordered_map>
#include <vector>

#include <fcntl.h>
#include <signal.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <time.h>
#include <unistd.h>

namespace {

constexpr int RXW_MAX_SEGMENTS = 4;
constexpr int RXW_MAX_THREADS = 8;
constexpr uint64_t RXW_WAKE = ~0ull;    // the wake eventfd's epoll datum
constexpr int RXW_FIELDS = 6;           // u64s a completion, see rxw_reap
constexpr uint64_t TXW_CHUNK = 1 << 20;     // crc this far ahead of the send
constexpr int TXW_IOV = 64;             // parts a sendmsg

// One stretch of a send: as it lies (the head, the preamble), hashed into
// its segment's crc on the way, or the four bytes that crc is written to.
enum TxKind { TX_PLAIN, TX_HASHED, TX_SLOT };

struct TxPart {
  uint8_t* base;
  uint64_t len;
  TxKind kind;
};

struct RxJob {            // a receive, or (`tx`) a send
  uint64_t token;
  int fd;                 // a receive's: a dup, closed when let go
  bool tx = false;        // a send's fd is the caller's, never closed here
  int notify_fd;          // the submitting loop's eventfd
  uint8_t* dst;
  std::atomic<uint64_t> have;   // bytes there / sent (read by rxw_progress)
  uint64_t len;           // bytes of the whole body / of everything to send
  int nseg;               // 0: no crc (an onwire blob)
  uint64_t seg_lens[RXW_MAX_SEGMENTS];
  // the crc pass: everything before `crc_pos` is hashed or compared
  uint64_t crc_pos = 0, seg_start = 0;
  int seg = 0;
  uint32_t crc = 0;
  int bad = -1;           // first segment whose crc mismatched
  uint64_t recvs = 0, cpu_ns = 0;   // recvs: system calls that moved bytes
  bool in_epoll = false;
  // a send: `parts[crc_i]` from `crc_off` is the next byte to hash, the
  // first `ready` bytes are final, `parts[send_i]` from `send_off` is the
  // next to leave
  std::vector<TxPart> parts;
  size_t crc_i = 0, send_i = 0;
  uint64_t crc_off = 0, send_off = 0, ready = 0;
};

struct RxDone {
  uint64_t token, got, recvs, cpu_ns;
  int64_t bad, status;    // status: 0 whole, -1 EOF, else errno
};

struct RxWorker {
  std::mutex m;
  std::condition_variable let_go;
  std::unordered_map<uint64_t, RxJob*> jobs;   // submitted, not yet done
  std::vector<uint64_t> fresh;                 // submitted, not yet seen
  std::deque<RxDone> done;
  uint64_t running = 0;   // the token the thread works on outside `m`
  bool stop = false;
  int epfd = -1, wakefd = -1;
  std::thread thread;
};

std::vector<RxWorker*> g_rxw;   // the threads; guarded by g_rxw_m
std::mutex g_rxw_m;

uint64_t thread_cpu_ns() {
  struct timespec ts;
  clock_gettime(CLOCK_THREAD_CPUTIME_ID, &ts);
  return (uint64_t)ts.tv_sec * 1000000000ull + (uint64_t)ts.tv_nsec;
}

// Hash what has arrived and is not hashed yet; compare each segment's
// trailing crc once its four bytes are there.
void rxw_crc_advance(RxJob* j) {
  const uint64_t have = j->have;
  while (j->seg < j->nseg) {
    uint64_t seg_end = j->seg_start + j->seg_lens[j->seg];
    if (j->crc_pos < seg_end) {
      uint64_t upto = have < seg_end ? have : seg_end;
      if (upto <= j->crc_pos) return;
      j->crc = crc32c(j->crc, j->dst + j->crc_pos, (size_t)(upto - j->crc_pos));
      j->crc_pos = upto;
      if (upto < seg_end) return;
    }
    if (have < seg_end + 4) return;
    const uint8_t* p = j->dst + seg_end;
    uint32_t want = (uint32_t)p[0] | ((uint32_t)p[1] << 8) |
                    ((uint32_t)p[2] << 16) | ((uint32_t)p[3] << 24);
    if (j->crc != want && j->bad < 0) j->bad = j->seg;
    j->seg++;
    j->seg_start = j->crc_pos = seg_end + 4;
    j->crc = 0;
  }
}

// The job's fd and buffers are the worker's no longer. Called under `m`.
void rxw_release(RxWorker* w, RxJob* j) {
  if (j->in_epoll) epoll_ctl(w->epfd, EPOLL_CTL_DEL, j->fd, nullptr);
  if (!j->tx) close(j->fd);
  w->jobs.erase(j->token);
  delete j;
}

// Receive until the body is whole (0), the peer is gone (-1, or the errno)
// or the socket is empty (1).
int64_t rxw_recv(RxJob* j) {
  if (j->have) rxw_crc_advance(j);  // the head the spill held
  while (j->have < j->len) {
    ssize_t r = recv(j->fd, j->dst + j->have, (size_t)(j->len - j->have),
                     MSG_DONTWAIT);
    if (r > 0) {
      j->have += (uint64_t)r;
      j->recvs++;
      rxw_crc_advance(j);
    } else if (r == 0) {
      return -1;
    } else if (errno == EINTR) {
      continue;
    } else if (errno == EAGAIN || errno == EWOULDBLOCK) {
      return 1;
    } else {
      return errno;
    }
  }
  return 0;
}

// Make up to `budget` more bytes of a send final: hash segment parts into
// the running crc and write it into the slot that follows them.
void txw_crc_advance(RxJob* j, uint64_t budget) {
  while (j->crc_i < j->parts.size()) {
    TxPart& p = j->parts[j->crc_i];
    if (p.kind == TX_HASHED) {
      if (!budget) return;
      uint64_t n = p.len - j->crc_off;
      if (n > budget) n = budget;
      j->crc = crc32c(j->crc, p.base + j->crc_off, (size_t)n);
      j->crc_off += n;
      j->ready += n;
      budget -= n;
      if (j->crc_off < p.len) return;
    } else {
      if (p.kind == TX_SLOT) {
        put_u32le(p.base, j->crc);
        j->crc = 0;
      }
      j->ready += p.len;
    }
    j->crc_i++;
    j->crc_off = 0;
  }
}

// Send what is final until the kernel has all of it (0), the connection is
// gone (the errno) or the socket is full (1: every crc is computed by then).
int64_t txw_send(RxJob* j) {
  for (;;) {
    uint64_t sent = j->have;
    if (sent == j->len) return 0;
    if (j->ready < j->len && j->ready - sent < TXW_CHUNK)
      txw_crc_advance(j, TXW_CHUNK);
    struct iovec iov[TXW_IOV];
    int n = 0;
    uint64_t room = j->ready - sent, off = j->send_off;
    for (size_t i = j->send_i; room && n < TXW_IOV; i++, off = 0) {
      const TxPart& p = j->parts[i];
      uint64_t take = p.len - off < room ? p.len - off : room;
      iov[n].iov_base = p.base + off;
      iov[n].iov_len = (size_t)take;
      n++;
      room -= take;
    }
    struct msghdr mh = {};
    mh.msg_iov = iov;
    mh.msg_iovlen = (size_t)n;
    ssize_t r = sendmsg(j->fd, &mh, MSG_DONTWAIT | MSG_NOSIGNAL);
    if (r >= 0) {
      j->recvs++;
      j->have += (uint64_t)r;
      uint64_t left = (uint64_t)r;
      while (left) {
        uint64_t in_part = j->parts[j->send_i].len - j->send_off;
        if (left < in_part) {
          j->send_off += left;
          break;
        }
        left -= in_part;
        j->send_i++;
        j->send_off = 0;
      }
    } else if (errno == EINTR) {
      continue;
    } else if (errno == EAGAIN || errno == EWOULDBLOCK) {
      txw_crc_advance(j, ~0ull);    // the wait pays for the rest of it
      return 1;
    } else {
      return errno;
    }
  }
}

// Work on one job until it is whole, the peer is gone, or the socket is
// empty (a receive) or full (a send). `w->running` is the job's token:
// nobody else touches it.
void rxw_work(RxWorker* w, RxJob* j) {
  uint64_t t0 = thread_cpu_ns();
  int64_t status = j->tx ? txw_send(j) : rxw_recv(j);
  if (status == 1 && !j->in_epoll) {
    struct epoll_event ev;
    ev.events = j->tx ? EPOLLOUT : EPOLLIN;
    ev.data.u64 = j->token;
    if (epoll_ctl(w->epfd, EPOLL_CTL_ADD, j->fd, &ev) == 0)
      j->in_epoll = true;
    else
      status = errno;
  }
  j->cpu_ns += thread_cpu_ns() - t0;
  std::lock_guard<std::mutex> g(w->m);
  w->running = 0;
  if (status != 1) {
    w->done.push_back(RxDone{j->token, j->have.load(), j->recvs, j->cpu_ns,
                             (int64_t)j->bad, status});
    // inside the lock: whoever reaps this completion may close the
    // eventfd next, and cannot reap before the lock is free
    uint64_t one = 1;
    ssize_t wr = write(j->notify_fd, &one, 8);
    (void)wr;
    rxw_release(w, j);
  }
  w->let_go.notify_all();
}

// Take the job `token` for the thread, unless it was cancelled meanwhile.
RxJob* rxw_take(RxWorker* w, uint64_t token) {
  std::lock_guard<std::mutex> g(w->m);
  auto it = w->jobs.find(token);
  if (it == w->jobs.end()) return nullptr;
  w->running = token;
  return it->second;
}

void rxw_main(RxWorker* w) {
  sigset_t all;           // the interpreter's signals are its own threads'
  sigfillset(&all);
  pthread_sigmask(SIG_BLOCK, &all, nullptr);
  struct epoll_event evs[64];
  std::vector<uint64_t> fresh;
  for (;;) {
    int n = epoll_wait(w->epfd, evs, 64, -1);
    if (n < 0) {
      if (errno == EINTR) continue;
      return;
    }
    for (int i = 0; i < n; i++) {
      uint64_t token = evs[i].data.u64;
      if (token != RXW_WAKE) {
        if (RxJob* j = rxw_take(w, token)) rxw_work(w, j);
        continue;
      }
      uint64_t count;
      ssize_t rd = read(w->wakefd, &count, 8);
      (void)rd;
      {
        std::lock_guard<std::mutex> g(w->m);
        if (w->stop) return;
        fresh.swap(w->fresh);
      }
      for (uint64_t t : fresh)
        if (RxJob* j = rxw_take(w, t)) rxw_work(w, j);
      fresh.clear();
    }
  }
}

void rxw_close_fds(RxWorker* w) {
  for (auto& kv : w->jobs) {
    if (!kv.second->tx) close(kv.second->fd);
    delete kv.second;
  }
  w->jobs.clear();
  if (w->epfd >= 0) close(w->epfd);
  if (w->wakefd >= 0) close(w->wakefd);
}

// One more thread. Under g_rxw_m. 0, or -errno with nothing left behind.
int rxw_start_one() {
  RxWorker* w = new RxWorker();
  w->epfd = epoll_create1(EPOLL_CLOEXEC);
  w->wakefd = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
  struct epoll_event ev;
  ev.events = EPOLLIN;
  ev.data.u64 = RXW_WAKE;
  if (w->epfd < 0 || w->wakefd < 0 ||
      epoll_ctl(w->epfd, EPOLL_CTL_ADD, w->wakefd, &ev) != 0) {
    int e = errno ? errno : EINVAL;
    rxw_close_fds(w);
    delete w;
    return -e;
  }
  try {
    w->thread = std::thread(rxw_main, w);
  } catch (...) {
    rxw_close_fds(w);
    delete w;
    return -EAGAIN;
  }
  g_rxw.push_back(w);
  return 0;
}

// Stop every thread, drop every job and close the workers' fds. Under
// g_rxw_m. The jobs that were still there.
int rxw_stop_all() {
  int left = 0;
  for (RxWorker* w : g_rxw) {
    {
      std::lock_guard<std::mutex> g2(w->m);
      w->stop = true;
    }
    uint64_t one = 1;
    ssize_t wr = write(w->wakefd, &one, 8);
    (void)wr;
    w->thread.join();
    left += (int)w->jobs.size();
    rxw_close_fds(w);
    delete w;
  }
  g_rxw.clear();
  return left;
}

// Enter `j` with the thread that serves the connection whose socket is
// `pin`. Under g_rxw_m, with threads running.
void rxw_enter(int pin, RxJob* j) {
  RxWorker* w = g_rxw[(size_t)(pin < 0 ? 0 : pin) % g_rxw.size()];
  {
    std::lock_guard<std::mutex> g2(w->m);
    w->jobs[j->token] = j;
    w->fresh.push_back(j->token);
  }
  uint64_t one = 1;
  ssize_t wr = write(w->wakefd, &one, 8);
  (void)wr;
}

}  // namespace

extern "C" {

// Start `threads` threads if none runs. 0, or -errno.
int rxw_start(int threads) {
  std::lock_guard<std::mutex> g(g_rxw_m);
  if (!g_rxw.empty()) return 0;
  if (threads < 1 || threads > RXW_MAX_THREADS) return -EINVAL;
  for (int i = 0; i < threads; i++) {
    int err = rxw_start_one();
    if (err) {
      rxw_stop_all();
      return err;
    }
  }
  return 0;
}

// Stop the threads, drop every job (their buffers are the caller's again)
// and close the workers' fds. The jobs still there, which the caller
// should have cancelled: their count.
int rxw_stop() {
  std::lock_guard<std::mutex> g(g_rxw_m);
  return rxw_stop_all();
}

// In the child of a fork, which has the workers' fds and not their threads:
// close the two fds a worker that are its alone and start anew. Whatever
// the parent's threads held or were changing at the fork is left alone
// (the tables are not walked, the structs are leaked); a job's dup stays
// open in the child as the socket it was made from does.
void rxw_forked() {
  new (&g_rxw_m) std::mutex();
  for (RxWorker* w : g_rxw) {
    close(w->epfd);
    close(w->wakefd);
  }
  new (&g_rxw) std::vector<RxWorker*>();
}

// The threads that run.
int rxw_running() {
  std::lock_guard<std::mutex> g(g_rxw_m);
  return (int)g_rxw.size();
}

// Jobs submitted and not yet completed or cancelled.
int rxw_jobs() {
  std::lock_guard<std::mutex> g(g_rxw_m);
  int n = 0;
  for (RxWorker* w : g_rxw) {
    std::lock_guard<std::mutex> g2(w->m);
    n += (int)w->jobs.size();
  }
  return n;
}

// Hand the worker `fd` (it works on a dup) for the rest of a body of `len`
// bytes at `dst`, of which `have` are there. `seg_lens[nseg]`: the frame's
// segments, each followed by its crc32c in the body; nseg 0 means no crc.
// `notify_fd` is written to when the completion can be reaped.
// 0, or -errno.
int rxw_submit(uint64_t token, int fd, uint8_t* dst, uint64_t have,
               uint64_t len, const uint64_t* seg_lens, int nseg,
               int notify_fd) {
  if (nseg < 0 || nseg > RXW_MAX_SEGMENTS || have >= len) return -EINVAL;
  std::lock_guard<std::mutex> g(g_rxw_m);
  if (g_rxw.empty()) return -ESRCH;
  int dupfd = fcntl(fd, F_DUPFD_CLOEXEC, 0);
  if (dupfd < 0) return -errno;
  RxJob* j = new RxJob();
  j->token = token;
  j->fd = dupfd;
  j->notify_fd = notify_fd;
  j->dst = dst;
  j->have = have;
  j->len = len;
  j->nseg = nseg;
  for (int i = 0; i < nseg; i++) j->seg_lens[i] = seg_lens[i];
  rxw_enter(fd, j);
  return 0;
}

// Hand the worker one frame to send on `fd`, which stays the caller's: a
// dup of the connection's socket `pin` that it keeps for the connection's
// life and closes after the last job on it is reaped or cancelled. The
// frame is frame_crcs' arguments; `hdr` (8 + 8*nseg bytes, the job's own)
// gets the preamble here and each segment's crc from the thread. `head`
// (`head_len` bytes, may be 0) leaves in front of the frame. Every part,
// `head` and `hdr` stay alive and unwritten until the job is reaped or
// cancelled. 0, or -errno.
int rxw_submit_tx(uint64_t token, int fd, int pin, int notify_fd,
                  uint8_t* head, uint64_t head_len,
                  uint32_t magic, uint32_t tag, int nseg,
                  const uint64_t* seg_parts, uint8_t* const* parts,
                  const uint64_t* part_lens, uint8_t* hdr) {
  if (nseg < 0 || nseg > RXW_MAX_SEGMENTS) return -EINVAL;
  std::lock_guard<std::mutex> g(g_rxw_m);
  if (g_rxw.empty()) return -ESRCH;
  RxJob* j = new RxJob();
  j->token = token;
  j->fd = fd;
  j->tx = true;
  j->notify_fd = notify_fd;
  j->have = 0;
  j->len = 0;
  auto add = [j](uint8_t* base, uint64_t len, TxKind kind) {
    j->parts.push_back(TxPart{base, len, kind});
    j->len += len;
  };
  uint8_t* slot = put_preamble(magic, tag, nseg, seg_parts, part_lens, hdr);
  if (head_len) add(head, head_len, TX_PLAIN);
  add(hdr, (uint64_t)(slot - hdr), TX_PLAIN);
  size_t part = 0;
  for (int s = 0; s < nseg; s++, slot += 4) {
    for (uint64_t k = 0; k < seg_parts[s]; k++, part++)
      if (part_lens[part]) add(parts[part], part_lens[part], TX_HASHED);
    add(slot, 4, TX_SLOT);
  }
  rxw_enter(pin, j);
  return 0;
}

// Take a job back. Returns once the thread has let go of its fd and
// buffers: the bytes that are there or were sent (>= 0), or -1 if the job
// is not the worker's any more (its completion is in the ring, or was
// reaped).
int64_t rxw_cancel(uint64_t token) {
  std::lock_guard<std::mutex> g(g_rxw_m);
  for (RxWorker* w : g_rxw) {
    std::unique_lock<std::mutex> l(w->m);
    w->let_go.wait(l, [&] { return w->running != token; });
    auto it = w->jobs.find(token);
    if (it == w->jobs.end()) continue;
    int64_t got = (int64_t)it->second->have.load();
    rxw_release(w, it->second);
    return got;
  }
  return -1;
}

// Bytes of job `token` that are there or were sent, or -1 if the worker
// has no such job (any more).
int64_t rxw_progress(uint64_t token) {
  std::lock_guard<std::mutex> g(g_rxw_m);
  for (RxWorker* w : g_rxw) {
    std::lock_guard<std::mutex> g2(w->m);
    auto it = w->jobs.find(token);
    if (it != w->jobs.end()) return (int64_t)it->second->have.load();
  }
  return -1;
}

// Up to `max` completions into `out`, six u64 each: token, bytes there or
// sent, system calls that moved some, the thread's CPU ns on the job, the
// first segment whose crc mismatched or -1 (a send: -1), and the status (0
// whole, -1 EOF, else the errno). Returns how many.
int rxw_reap(uint64_t* out, int max) {
  std::lock_guard<std::mutex> g(g_rxw_m);
  int n = 0;
  for (RxWorker* w : g_rxw) {
    std::lock_guard<std::mutex> g2(w->m);
    while (n < max && !w->done.empty()) {
      const RxDone& d = w->done.front();
      uint64_t* o = out + n * RXW_FIELDS;
      o[0] = d.token; o[1] = d.got; o[2] = d.recvs; o[3] = d.cpu_ns;
      o[4] = (uint64_t)d.bad; o[5] = (uint64_t)d.status;
      w->done.pop_front();
      n++;
    }
  }
  return n;
}

}  // extern "C"

#endif  // __linux__
