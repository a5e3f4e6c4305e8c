// peerlink: the native serving shim (SURVEY §2.3 native tier).
//
// The reference's peer hop is a Go gRPC unary call measured at ~30 µs
// typical (reference: README.md:104, peer_client.go:127-140). A Python
// gRPC server pays the GIL + HTTP/2 + protobuf machinery PER RPC (~0.4 ms,
// ~2.3k unbatched RPC/s); this shim moves everything per-RPC off the GIL:
//
//   accept / read / frame parse / micro-batch aggregation  -> C++ (here)
//   rate-limit decision                                    -> Python,
//         entered once per BATCH via a blocking, GIL-released puller
//
// Wire protocol (internal - both ends are this framework; the public gRPC
// surface stays wire-compatible with the reference and is served by the
// Python tier unchanged):
//
// Frames are COLUMNAR — the same staging-format philosophy as the device
// path: a batch's fields ride as contiguous arrays, so both ends encode
// and decode with bulk copies (numpy on the Python side, memcpy here)
// instead of per-item marshalling:
//
//   request frame := u32 len | u64 rid | u8 method | u16 count
//                  | u16 name_len[count] | u16 ukey_len[count]
//                  | keys blob (name_i + ukey_i, item order)
//                  | i64 hits[count] | i64 limit[count]
//                  | i64 duration[count]
//                  | u32 algorithm[count] | u32 behavior[count]
//   reply frame   := u32 len | u64 rid | u8 method | u16 count
//                  | i32 status[count] | i64 limit[count]
//                  | i64 remaining[count] | i64 reset[count]
//                  | u16 err_len[count] | err blob
//
// name and unique_key ride as separate fields (splitting a concatenated
// hash_key would mis-attribute embedded underscores and diverge from the
// gRPC tier's validation). count must be 1..1024; each field <= 1024 B —
// the CLIENT pre-checks and falls back to gRPC for anything bigger.
//
// method 0 = GetRateLimits (public lean surface, router semantics),
// method 1 = GetPeerRateLimits (owner apply). Responses echo rid/method.
//
// ---- wire contract v2 (docs/wire.md) ----
// Real methods occupy 0x00..0xE1 (method | carrier flags 0x80/0x40/0x20);
// the 0xF0..0xFF method range is reserved for CONTROL frames:
//
//   0xF0 GREETING  server -> client, sent on accept when the server can
//                  speak v2. Shaped as a valid v1 reply frame (rid 0,
//                  count 1, version in the status column) so a v1 client
//                  parses it and drops the unknown rid silently.
//   0xF1 HELLO     client -> server, sent only after a GREETING (so it
//                  never reaches a v1 server). Body is the bare 11-byte
//                  header; count carries the client's max version. Flips
//                  the conn to v2.
//   0xF2 PARTIAL   server -> client, v2 reply streaming: one contiguous
//                  row-span of a rid's reply, sent as soon as the span's
//                  rows finalize —
//     u32 len | u64 rid | u8 0xF2 | u16 count | u16 seq | u16 base
//             | u8 final | i32 status[count] | i64 limit[count]
//             | i64 remaining[count] | i64 reset[count]
//             | u16 err_len[count] | err blob
//   seq is per-rid send order (client checks it), base the row offset
//   inside the original request frame, final=1 on the span that
//   completes the rid. Spans of DIFFERENT rids interleave freely; spans
//   of one rid are seq-ordered. A whole v1 reply frame may still arrive
//   for any rid (native fast path, error fill) and is authoritative.
//
// Threading: one epoll IO thread owns every socket. Parsed frames land on
// a mutex+condvar queue; Python worker threads block in pls_next_batch()
// (ctypes CDLL call -> GIL dropped) and wake with EVERYTHING pending —
// the same dispatch-latency adaptive batching as service/combiner.py: a
// lone request wakes a worker immediately (no fixed window), a herd
// aggregates while the workers are busy. Responses are handed back as
// arrays; the IO thread serializes and writes them (eventfd-kicked).

#include <arpa/inet.h>
#include <atomic>
#include <cerrno>
#include <chrono>
#include <cstdint>
#include <cstring>
#include <cstdio>
#include <condition_variable>
#include <deque>
#include <fcntl.h>
#include <map>
#include <memory>
#include <mutex>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <string>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <thread>
#include <unistd.h>
#include <vector>

namespace {

constexpr uint32_t kMaxFrame = 4u << 20;  // 4 MB, > 1000-item batches

// v2 control methods (header comment: "wire contract v2")
constexpr uint8_t kMethodGreeting = 0xF0;
constexpr uint8_t kMethodHello = 0xF1;
constexpr uint8_t kMethodPartial = 0xF2;

// The native lone-request fast path (VERDICT r2 item 6): a 1-item
// GetPeerRateLimits frame can be decided right here in the IO thread —
// keydir.cpp's decide_one against the key's row mirror — and answered
// without waking a Python worker, without the GIL, without a kernel
// dispatch. The signature matches keydir_decide_one's C ABI.
using NativeDecideFn = int (*)(void*, const char*, int32_t, int64_t,
                               int64_t, int64_t, int32_t, int32_t, int64_t,
                               int64_t*);

// CLOCK_MONOTONIC, the clock Python's time.perf_counter_ns() reads on
// Linux: the front's stamps lay beside the engine's without translation.
inline int64_t mono_ns() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

// obs/profile.py PhaseHist in C: bucket i holds observations
// <= 2^(i+kHistShift) ns, so Python reads the counts as its own. Every
// writer and pls_profile hold s->mu.
constexpr int kHistShift = 10;
constexpr int kHistBuckets = 28;
struct Hist {
  int64_t counts[kHistBuckets] = {};
  int64_t n = 0, total_ns = 0, max_ns = 0;
  void observe(int64_t ns) {
    if (ns < 0) ns = 0;
    int idx = ns ? 64 - __builtin_clzll((unsigned long long)ns) - kHistShift
                 : 0;
    if (idx < 0) idx = 0;
    if (idx >= kHistBuckets) idx = kHistBuckets - 1;
    counts[idx]++;
    n++;
    total_ns += ns;
    if (ns > max_ns) max_ns = ns;
  }
};

struct Frame {
  uint64_t conn_token;
  uint64_t rid;
  uint8_t method;
  uint16_t count = 0;
  int64_t arrive_ns = 0;  // IO thread, when the frame's last byte was parsed
  int64_t parse_ns = 0;   // wire bytes -> these columns
  // columnar request payload, exactly as parsed off the wire
  std::vector<uint16_t> name_len, ukey_len;
  std::string keys;  // name_i + ukey_i concatenated in item order
  std::vector<int64_t> hits, limit, duration;
  std::vector<uint32_t> algorithm, behavior;
};

struct PendingReply {
  int64_t arrive_ns = 0;  // the frame's stamp (the frame moves out at the pull)
  uint8_t method = 0;
  uint16_t expected = 0;
  uint16_t got = 0;
  uint32_t h2_stream = 0;  // nonzero: reply as a gRPC/H2 response
  uint16_t next_seq = 0;   // v2 streaming: per-rid partial-frame order
  // The conn's negotiated version WHEN THIS RID WAS PARSED. The HELLO
  // races the client's first request frames (the client pipelines without
  // waiting for the greeting round-trip), so a rid parsed pre-upgrade may
  // start accumulating v1-style while the conn flips to v2 under it —
  // branching on c->wire_version at post time would then stream only the
  // post-upgrade spans and the client's reassembly would end with holes.
  // Latching per-rid makes every rid all-whole-frame or all-partial.
  bool wire_v2 = false;
  // columnar reply assembly, by item index
  std::vector<int32_t> status;
  std::vector<int64_t> limit, remaining, reset;
  std::vector<std::string> err;
  std::vector<std::string> meta;  // pre-encoded pb field-6 bytes (H2 only)
  std::vector<uint8_t> filled;
};

// ===========================================================================
// gRPC-over-HTTP/2 front (VERDICT r3 item 2): real gRPC framing on this
// epoll loop, so existing gubernator clients (grpc-go, grpcio) talk
// DIRECTLY to the native tier — no Python, no GIL, per RPC. A connection
// accepted on the gRPC listener speaks RFC 7540 HTTP/2 + RFC 7541 HPACK;
// unary GetRateLimits / GetPeerRateLimits bodies parse (hand-rolled
// protobuf for the fixed field set, proto/gubernator.proto:46-67) into the
// SAME columnar Frame queue the internal link protocol feeds — the Python
// batch workers and the IO-thread native fast path serve both wire
// protocols without knowing which one a request arrived on. Anything the
// C parser cannot take verbatim (unknown fields, oversized, compressed
// messages, other methods like UpdatePeerGlobals) is punted to Python as
// raw bytes (pls_next_raw/pls_send_raw) and answered by the same servicer
// objects the grpcio server binds — full wire compatibility, C fast lane.
// ===========================================================================

// ---------------------------------------------------------------- HPACK
struct HuffCode { uint32_t code; uint8_t bits; };
// RFC 7541 Appendix B code table (symbols 0-255 + EOS)
const HuffCode kHuff[257] = {
    {0x1ff8u, 13}, {0x7fffd8u, 23}, {0xfffffe2u, 28}, {0xfffffe3u, 28}, {0xfffffe4u, 28}, {0xfffffe5u, 28}, {0xfffffe6u, 28}, {0xfffffe7u, 28},
    {0xfffffe8u, 28}, {0xffffeau, 24}, {0x3ffffffcu, 30}, {0xfffffe9u, 28}, {0xfffffeau, 28}, {0x3ffffffdu, 30}, {0xfffffebu, 28}, {0xfffffecu, 28},
    {0xfffffedu, 28}, {0xfffffeeu, 28}, {0xfffffefu, 28}, {0xffffff0u, 28}, {0xffffff1u, 28}, {0xffffff2u, 28}, {0x3ffffffeu, 30}, {0xffffff3u, 28},
    {0xffffff4u, 28}, {0xffffff5u, 28}, {0xffffff6u, 28}, {0xffffff7u, 28}, {0xffffff8u, 28}, {0xffffff9u, 28}, {0xffffffau, 28}, {0xffffffbu, 28},
    {0x14u, 6}, {0x3f8u, 10}, {0x3f9u, 10}, {0xffau, 12}, {0x1ff9u, 13}, {0x15u, 6}, {0xf8u, 8}, {0x7fau, 11},
    {0x3fau, 10}, {0x3fbu, 10}, {0xf9u, 8}, {0x7fbu, 11}, {0xfau, 8}, {0x16u, 6}, {0x17u, 6}, {0x18u, 6},
    {0x0u, 5}, {0x1u, 5}, {0x2u, 5}, {0x19u, 6}, {0x1au, 6}, {0x1bu, 6}, {0x1cu, 6}, {0x1du, 6},
    {0x1eu, 6}, {0x1fu, 6}, {0x5cu, 7}, {0xfbu, 8}, {0x7ffcu, 15}, {0x20u, 6}, {0xffbu, 12}, {0x3fcu, 10},
    {0x1ffau, 13}, {0x21u, 6}, {0x5du, 7}, {0x5eu, 7}, {0x5fu, 7}, {0x60u, 7}, {0x61u, 7}, {0x62u, 7},
    {0x63u, 7}, {0x64u, 7}, {0x65u, 7}, {0x66u, 7}, {0x67u, 7}, {0x68u, 7}, {0x69u, 7}, {0x6au, 7},
    {0x6bu, 7}, {0x6cu, 7}, {0x6du, 7}, {0x6eu, 7}, {0x6fu, 7}, {0x70u, 7}, {0x71u, 7}, {0x72u, 7},
    {0xfcu, 8}, {0x73u, 7}, {0xfdu, 8}, {0x1ffbu, 13}, {0x7fff0u, 19}, {0x1ffcu, 13}, {0x3ffcu, 14}, {0x22u, 6},
    {0x7ffdu, 15}, {0x3u, 5}, {0x23u, 6}, {0x4u, 5}, {0x24u, 6}, {0x5u, 5}, {0x25u, 6}, {0x26u, 6},
    {0x27u, 6}, {0x6u, 5}, {0x74u, 7}, {0x75u, 7}, {0x28u, 6}, {0x29u, 6}, {0x2au, 6}, {0x7u, 5},
    {0x2bu, 6}, {0x76u, 7}, {0x2cu, 6}, {0x8u, 5}, {0x9u, 5}, {0x2du, 6}, {0x77u, 7}, {0x78u, 7},
    {0x79u, 7}, {0x7au, 7}, {0x7bu, 7}, {0x7ffeu, 15}, {0x7fcu, 11}, {0x3ffdu, 14}, {0x1ffdu, 13}, {0xffffffcu, 28},
    {0xfffe6u, 20}, {0x3fffd2u, 22}, {0xfffe7u, 20}, {0xfffe8u, 20}, {0x3fffd3u, 22}, {0x3fffd4u, 22}, {0x3fffd5u, 22}, {0x7fffd9u, 23},
    {0x3fffd6u, 22}, {0x7fffdau, 23}, {0x7fffdbu, 23}, {0x7fffdcu, 23}, {0x7fffddu, 23}, {0x7fffdeu, 23}, {0xffffebu, 24}, {0x7fffdfu, 23},
    {0xffffecu, 24}, {0xffffedu, 24}, {0x3fffd7u, 22}, {0x7fffe0u, 23}, {0xffffeeu, 24}, {0x7fffe1u, 23}, {0x7fffe2u, 23}, {0x7fffe3u, 23},
    {0x7fffe4u, 23}, {0x1fffdcu, 21}, {0x3fffd8u, 22}, {0x7fffe5u, 23}, {0x3fffd9u, 22}, {0x7fffe6u, 23}, {0x7fffe7u, 23}, {0xffffefu, 24},
    {0x3fffdau, 22}, {0x1fffddu, 21}, {0xfffe9u, 20}, {0x3fffdbu, 22}, {0x3fffdcu, 22}, {0x7fffe8u, 23}, {0x7fffe9u, 23}, {0x1fffdeu, 21},
    {0x7fffeau, 23}, {0x3fffddu, 22}, {0x3fffdeu, 22}, {0xfffff0u, 24}, {0x1fffdfu, 21}, {0x3fffdfu, 22}, {0x7fffebu, 23}, {0x7fffecu, 23},
    {0x1fffe0u, 21}, {0x1fffe1u, 21}, {0x3fffe0u, 22}, {0x1fffe2u, 21}, {0x7fffedu, 23}, {0x3fffe1u, 22}, {0x7fffeeu, 23}, {0x7fffefu, 23},
    {0xfffeau, 20}, {0x3fffe2u, 22}, {0x3fffe3u, 22}, {0x3fffe4u, 22}, {0x7ffff0u, 23}, {0x3fffe5u, 22}, {0x3fffe6u, 22}, {0x7ffff1u, 23},
    {0x3ffffe0u, 26}, {0x3ffffe1u, 26}, {0xfffebu, 20}, {0x7fff1u, 19}, {0x3fffe7u, 22}, {0x7ffff2u, 23}, {0x3fffe8u, 22}, {0x1ffffecu, 25},
    {0x3ffffe2u, 26}, {0x3ffffe3u, 26}, {0x3ffffe4u, 26}, {0x7ffffdeu, 27}, {0x7ffffdfu, 27}, {0x3ffffe5u, 26}, {0xfffff1u, 24}, {0x1ffffedu, 25},
    {0x7fff2u, 19}, {0x1fffe3u, 21}, {0x3ffffe6u, 26}, {0x7ffffe0u, 27}, {0x7ffffe1u, 27}, {0x3ffffe7u, 26}, {0x7ffffe2u, 27}, {0xfffff2u, 24},
    {0x1fffe4u, 21}, {0x1fffe5u, 21}, {0x3ffffe8u, 26}, {0x3ffffe9u, 26}, {0xffffffdu, 28}, {0x7ffffe3u, 27}, {0x7ffffe4u, 27}, {0x7ffffe5u, 27},
    {0xfffecu, 20}, {0xfffff3u, 24}, {0xfffedu, 20}, {0x1fffe6u, 21}, {0x3fffe9u, 22}, {0x1fffe7u, 21}, {0x1fffe8u, 21}, {0x7ffff3u, 23},
    {0x3fffeau, 22}, {0x3fffebu, 22}, {0x1ffffeeu, 25}, {0x1ffffefu, 25}, {0xfffff4u, 24}, {0xfffff5u, 24}, {0x3ffffeau, 26}, {0x7ffff4u, 23},
    {0x3ffffebu, 26}, {0x7ffffe6u, 27}, {0x3ffffecu, 26}, {0x3ffffedu, 26}, {0x7ffffe7u, 27}, {0x7ffffe8u, 27}, {0x7ffffe9u, 27}, {0x7ffffeau, 27},
    {0x7ffffebu, 27}, {0xffffffeu, 28}, {0x7ffffecu, 27}, {0x7ffffedu, 27}, {0x7ffffeeu, 27}, {0x7ffffefu, 27}, {0x7fffff0u, 27}, {0x3ffffeeu, 26},
    {0x3fffffffu, 30},
};

struct HuffNode { int16_t child[2]; int16_t sym; };  // sym -1 interior, -2 EOS

const std::vector<HuffNode>& huff_tree() {
  static const std::vector<HuffNode>* tree = [] {
    auto* v = new std::vector<HuffNode>;
    v->push_back({{-1, -1}, -1});
    for (int s = 0; s < 257; s++) {
      int n = 0;
      for (int b = kHuff[s].bits - 1; b >= 0; b--) {
        const int bit = (kHuff[s].code >> b) & 1;
        if ((*v)[n].child[bit] < 0) {
          (*v)[n].child[bit] = (int16_t)v->size();
          v->push_back({{-1, -1}, -1});
        }
        n = (*v)[n].child[bit];
      }
      (*v)[n].sym = (int16_t)(s == 256 ? -2 : s);
    }
    return v;
  }();
  return *tree;
}

bool huff_decode(const uint8_t* p, size_t len, std::string* out) {
  const auto& t = huff_tree();
  int n = 0, depth = 0;
  bool all_ones = true;  // padding must be a prefix of EOS (all 1 bits)
  for (size_t i = 0; i < len; i++) {
    for (int b = 7; b >= 0; b--) {
      const int bit = (p[i] >> b) & 1;
      n = t[n].child[bit];
      if (n < 0) return false;
      depth++;
      all_ones = all_ones && bit;
      if (t[n].sym != -1) {
        if (t[n].sym == -2) return false;  // EOS inside the stream
        out->push_back((char)t[n].sym);
        n = 0;
        depth = 0;
        all_ones = true;
      }
    }
  }
  return depth <= 7 && all_ones;  // RFC 7541 §5.2 padding rules
}

// RFC 7541 Appendix A static table (1-based indices 1..61)
const char* const kHpackStatic[61][2] = {
    {":authority", ""}, {":method", "GET"}, {":method", "POST"},
    {":path", "/"}, {":path", "/index.html"}, {":scheme", "http"},
    {":scheme", "https"}, {":status", "200"}, {":status", "204"},
    {":status", "206"}, {":status", "304"}, {":status", "400"},
    {":status", "404"}, {":status", "500"}, {"accept-charset", ""},
    {"accept-encoding", "gzip, deflate"}, {"accept-language", ""},
    {"accept-ranges", ""}, {"accept", ""},
    {"access-control-allow-origin", ""}, {"age", ""}, {"allow", ""},
    {"authorization", ""}, {"cache-control", ""},
    {"content-disposition", ""}, {"content-encoding", ""},
    {"content-language", ""}, {"content-length", ""},
    {"content-location", ""}, {"content-range", ""}, {"content-type", ""},
    {"cookie", ""}, {"date", ""}, {"etag", ""}, {"expect", ""},
    {"expires", ""}, {"from", ""}, {"host", ""}, {"if-match", ""},
    {"if-modified-since", ""}, {"if-none-match", ""}, {"if-range", ""},
    {"if-unmodified-since", ""}, {"last-modified", ""}, {"link", ""},
    {"location", ""}, {"max-forwards", ""}, {"proxy-authenticate", ""},
    {"proxy-authorization", ""}, {"range", ""}, {"referer", ""},
    {"refresh", ""}, {"retry-after", ""}, {"server", ""},
    {"set-cookie", ""}, {"strict-transport-security", ""},
    {"transfer-encoding", ""}, {"user-agent", ""}, {"vary", ""},
    {"via", ""}, {"www-authenticate", ""}};

struct HpackDec {
  // dynamic table, front = most recent (index 62 onward)
  std::deque<std::pair<std::string, std::string>> dyn;
  size_t dyn_bytes = 0;
  size_t max_bytes = 4096;  // peer may resize up to our SETTINGS cap

  void evict() {
    while (dyn_bytes > max_bytes && !dyn.empty()) {
      dyn_bytes -= dyn.back().first.size() + dyn.back().second.size() + 32;
      dyn.pop_back();
    }
  }
  void insert(std::string n, std::string v) {
    dyn_bytes += n.size() + v.size() + 32;
    dyn.emplace_front(std::move(n), std::move(v));
    evict();
  }
  bool lookup(uint64_t idx, std::string* n, std::string* v) const {
    if (idx == 0) return false;
    if (idx <= 61) {
      *n = kHpackStatic[idx - 1][0];
      *v = kHpackStatic[idx - 1][1];
      return true;
    }
    const uint64_t d = idx - 62;
    if (d >= dyn.size()) return false;
    *n = dyn[d].first;
    *v = dyn[d].second;
    return true;
  }
};

bool hp_int(const uint8_t*& p, const uint8_t* end, int prefix,
            uint64_t* out) {
  if (p >= end) return false;
  const uint64_t mask = (1u << prefix) - 1;
  uint64_t v = *p++ & mask;
  if (v < mask) {
    *out = v;
    return true;
  }
  int shift = 0;
  while (p < end) {
    const uint8_t b = *p++;
    v += (uint64_t)(b & 0x7f) << shift;
    if (!(b & 0x80)) {
      if (v > (1ull << 32)) return false;  // sanity bound
      *out = v;
      return true;
    }
    shift += 7;
    if (shift > 35) return false;
  }
  return false;
}

bool hp_str(const uint8_t*& p, const uint8_t* end, std::string* out) {
  if (p >= end) return false;
  const bool huff = (*p & 0x80) != 0;
  uint64_t len;
  if (!hp_int(p, end, 7, &len)) return false;
  if (len > 64 * 1024 || (uint64_t)(end - p) < len) return false;
  if (huff) {
    if (!huff_decode(p, (size_t)len, out)) return false;
  } else {
    out->assign((const char*)p, (size_t)len);
  }
  p += len;
  return true;
}

// Decode one complete header block, maintaining the connection's dynamic
// table; captures :path. Returns false on any HPACK violation.
bool hpack_decode_block(HpackDec* hp, const std::string& block,
                       std::string* path) {
  const uint8_t* p = (const uint8_t*)block.data();
  const uint8_t* end = p + block.size();
  while (p < end) {
    const uint8_t b = *p;
    std::string name, value;
    if (b & 0x80) {  // indexed
      uint64_t idx;
      if (!hp_int(p, end, 7, &idx)) return false;
      if (!hp->lookup(idx, &name, &value)) return false;
    } else if (b & 0x40) {  // literal with incremental indexing
      uint64_t idx;
      if (!hp_int(p, end, 6, &idx)) return false;
      if (idx) {
        std::string dummy;
        if (!hp->lookup(idx, &name, &dummy)) return false;
      } else if (!hp_str(p, end, &name)) {
        return false;
      }
      if (!hp_str(p, end, &value)) return false;
      hp->insert(name, value);
    } else if ((b & 0xe0) == 0x20) {  // dynamic table size update
      uint64_t sz;
      if (!hp_int(p, end, 5, &sz)) return false;
      if (sz > 4096) return false;  // our advertised SETTINGS cap
      hp->max_bytes = (size_t)sz;
      hp->evict();
      continue;
    } else {  // literal without indexing / never indexed
      uint64_t idx;
      if (!hp_int(p, end, 4, &idx)) return false;
      if (idx) {
        std::string dummy;
        if (!hp->lookup(idx, &name, &dummy)) return false;
      } else if (!hp_str(p, end, &name)) {
        return false;
      }
      if (!hp_str(p, end, &value)) return false;
    }
    if (path && name == ":path") *path = value;
  }
  return true;
}

// ------------------------------------------------------ protobuf (fixed)
// Hand-rolled codec for exactly proto/gubernator.proto's field set — any
// deviation punts the call to Python rather than risking silent drift.

bool pb_varint(const uint8_t*& p, const uint8_t* end, uint64_t* out) {
  uint64_t v = 0;
  int shift = 0;
  while (p < end && shift < 64) {
    const uint8_t b = *p++;
    v |= (uint64_t)(b & 0x7f) << shift;
    if (!(b & 0x80)) {
      *out = v;
      return true;
    }
    shift += 7;
  }
  return false;
}

void pb_put_varint(std::string* o, uint64_t v) {
  while (v >= 0x80) {
    o->push_back((char)(v | 0x80));
    v >>= 7;
  }
  o->push_back((char)v);
}

void pb_put_tag(std::string* o, int field, int wt) {
  pb_put_varint(o, (uint64_t)(field << 3 | wt));
}

// Parse one RateLimitReq submessage into the next Frame lane (appending
// to f->keys). Returns 1 ok, 0 = punt to Python, -1 malformed.
int pb_parse_rate_limit_req(const uint8_t* p, const uint8_t* end,
                            Frame* f) {
  std::string name, ukey;
  int64_t hits = 0, limit = 0, duration = 0;
  uint64_t algorithm = 0, behavior = 0;
  while (p < end) {
    uint64_t tag;
    if (!pb_varint(p, end, &tag)) return -1;
    const int field = (int)(tag >> 3), wt = (int)(tag & 7);
    if (wt == 2) {
      uint64_t len;
      if (!pb_varint(p, end, &len)) return -1;
      if ((uint64_t)(end - p) < len) return -1;
      if (field == 1) name.assign((const char*)p, (size_t)len);
      else if (field == 2) ukey.assign((const char*)p, (size_t)len);
      else return 0;  // metadata map / unknown: punt
      p += len;
    } else if (wt == 0) {
      uint64_t v;
      if (!pb_varint(p, end, &v)) return -1;
      switch (field) {
        case 3: hits = (int64_t)v; break;
        case 4: limit = (int64_t)v; break;
        case 5: duration = (int64_t)v; break;
        case 6: algorithm = v; break;
        case 7: behavior = v; break;
        default: return 0;  // unknown scalar: punt
      }
    } else {
      return 0;  // unexpected wire type: punt
    }
  }
  if (name.size() > 1024 || ukey.size() > 1024) return 0;
  f->name_len.push_back((uint16_t)name.size());
  f->ukey_len.push_back((uint16_t)ukey.size());
  f->keys += name;
  f->keys += ukey;
  f->hits.push_back(hits);
  f->limit.push_back(limit);
  f->duration.push_back(duration);
  f->algorithm.push_back((uint32_t)algorithm);
  f->behavior.push_back((uint32_t)behavior);
  return 1;
}

// GetRateLimitsReq / GetPeerRateLimitsReq (same shape: repeated field 1).
int pb_parse_get_rate_limits(const uint8_t* p, const uint8_t* end,
                             Frame* f) {
  while (p < end) {
    uint64_t tag;
    if (!pb_varint(p, end, &tag)) return -1;
    if (tag != (1 << 3 | 2)) return 0;  // only field-1 submessages
    uint64_t len;
    if (!pb_varint(p, end, &len)) return -1;
    if ((uint64_t)(end - p) < len) return -1;
    const int r = pb_parse_rate_limit_req(p, p + len, f);
    if (r != 1) return r;
    p += len;
    if (f->name_len.size() > 1024) return 0;  // frame cap: punt
  }
  f->count = (uint16_t)f->name_len.size();
  return f->count > 0 ? 1 : 0;  // empty request: punt (python replies)
}

// One RateLimitResp appended as field 1 of the response message. proto3
// canonical form: zero-valued scalars are omitted.
void pb_put_resp_item(std::string* o, int32_t status, int64_t limit,
                      int64_t remaining, int64_t reset,
                      const std::string& err,
                      const std::string& meta = std::string()) {
  std::string item;
  if (status) {
    pb_put_tag(&item, 1, 0);
    pb_put_varint(&item, (uint64_t)status);
  }
  if (limit) {
    pb_put_tag(&item, 2, 0);
    pb_put_varint(&item, (uint64_t)limit);
  }
  if (remaining) {
    pb_put_tag(&item, 3, 0);
    pb_put_varint(&item, (uint64_t)remaining);
  }
  if (reset) {
    pb_put_tag(&item, 4, 0);
    pb_put_varint(&item, (uint64_t)reset);
  }
  if (!err.empty()) {
    pb_put_tag(&item, 5, 2);
    pb_put_varint(&item, err.size());
    item += err;
  }
  item += meta;  // caller-encoded field-6 map entries, appended verbatim
  pb_put_tag(o, 1, 2);
  pb_put_varint(o, item.size());
  *o += item;
}

// ------------------------------------------------------------- HTTP/2
constexpr uint8_t H2_DATA = 0, H2_HEADERS = 1,
                  H2_RST_STREAM = 3, H2_SETTINGS = 4, H2_PING = 6,
                  H2_GOAWAY = 7, H2_WINDOW_UPDATE = 8, H2_CONTINUATION = 9;
constexpr uint8_t H2F_END_STREAM = 0x1, H2F_ACK = 0x1,
                  H2F_END_HEADERS = 0x4, H2F_PADDED = 0x8,
                  H2F_PRIORITY = 0x20;
const char kH2Preface[] = "PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n";
constexpr size_t kH2PrefaceLen = 24;
constexpr size_t kMaxH2Body = 4u << 20;  // matches kMaxFrame
constexpr uint32_t kH2MaxStreams = 1024;   // advertised + enforced
constexpr size_t kH2MaxBuffered = 64u << 20;  // per-conn request memory

struct H2Stream {
  std::string hdr_block;
  std::string body;
  std::string path;
  bool hdr_end = false;
  bool end_stream = false;
};

void h2_frame_hdr(std::string* o, uint32_t len, uint8_t type, uint8_t flags,
                  uint32_t sid) {
  o->push_back((char)(len >> 16));
  o->push_back((char)(len >> 8));
  o->push_back((char)len);
  o->push_back((char)type);
  o->push_back((char)flags);
  o->push_back((char)(sid >> 24 & 0x7f));
  o->push_back((char)(sid >> 16));
  o->push_back((char)(sid >> 8));
  o->push_back((char)sid);
}

// Response header block: ":status: 200" (static idx 8) + content-type
// (literal w/o indexing, static name idx 31). We never insert into the
// peer's decoder table, so there is no encoder state to corrupt.
std::string h2_resp_headers_block() {
  std::string b;
  b.push_back((char)0x88);
  b.push_back((char)0x0f);  // literal w/o indexing, name idx 31 = 15+16
  b.push_back((char)0x10);
  static const char ct[] = "application/grpc";
  b.push_back((char)(sizeof(ct) - 1));
  b.append(ct, sizeof(ct) - 1);
  return b;
}

void hp_put_literal(std::string* b, const char* name, size_t nlen,
                    const std::string& value) {
  b->push_back((char)0x00);  // literal w/o indexing, new name
  b->push_back((char)nlen);  // header names here are short (< 127)
  b->append(name, nlen);
  if (value.size() < 127) {
    b->push_back((char)value.size());
    *b += value;
  } else {
    b->push_back((char)0x7f);
    uint64_t rest = value.size() - 127;
    while (rest >= 0x80) {
      b->push_back((char)(rest | 0x80));
      rest >>= 7;
    }
    b->push_back((char)rest);
    *b += value;
  }
}

struct Conn {
  int fd = -1;
  uint64_t token = 0;
  std::string inbuf;
  // ---- gRPC/HTTP/2 connections (accepted on the grpc listener) ----
  bool h2 = false;
  bool preface_ok = false;
  HpackDec hpack;
  std::map<uint32_t, H2Stream> streams;
  uint32_t cont_stream = 0;     // stream awaiting CONTINUATION (0 = none)
  uint32_t max_frame_send = 16384;  // peer SETTINGS_MAX_FRAME_SIZE
  int64_t send_window = 65535;  // connection-level; DATA gated on it
  int64_t peer_initial_window = 65535;  // per-stream send budget
  // stream credit granted BEFORE the response was built (RFC 7540 §6.9:
  // WINDOW_UPDATE may precede our HEADERS; losing it can stall a
  // response forever when the peer's initial window is small)
  std::map<uint32_t, int64_t> stream_credit;
  size_t buffered_bytes = 0;  // total body+header bytes across streams
  // responses whose DATA exceeds a window: sent incrementally as the
  // peer's WINDOW_UPDATEs arrive (payload = gRPC-framed bytes; trailers
  // follow the final DATA frame)
  struct BlockedResp {
    uint32_t sid;
    std::string payload;
    size_t off = 0;
    int64_t stream_window;  // remaining per-stream budget
  };
  std::deque<BlockedResp> blocked;
  // write side is shared between the IO thread (EPOLLOUT flush) and
  // responder threads (direct send from pls_send_responses): wmu guards
  // outbuf + want_write + the fd's send() — two unsynchronized writers
  // would interleave frame bytes
  std::mutex wmu;
  std::string outbuf;
  bool want_write = false;
  std::map<uint64_t, PendingReply> pending;  // rid -> reply assembly
  // negotiated wire contract (guarded by s->mu): 1 until the client's
  // HELLO lands; h2 conns never negotiate (gRPC framing is the contract)
  int wire_version = 1;
};

struct Server {
  int listen_fd = -1;
  int epoll_fd = -1;
  int wake_fd = -1;  // eventfd: outbox kicks the IO thread
  std::thread io;
  bool stopping = false;

  std::mutex mu;  // guards queue + conns map
  std::condition_variable cv;
  std::deque<Frame> queue;  // parsed request frames awaiting a puller
  std::map<uint64_t, std::unique_ptr<Conn>> conns;  // token -> conn
  uint64_t next_token = 2;  // 0 = columnar listener, 1 = grpc listener
  int port = 0;

  // ---- gRPC/HTTP/2 front ----
  int grpc_listen_fd = -1;
  int grpc_port = 0;
  struct RawReq {  // calls the C parser punts to Python (full pb bytes)
    uint64_t conn_token;
    uint32_t stream_id;
    std::string path, body;
  };
  std::deque<RawReq> raw_queue;  // guarded by mu
  std::condition_variable raw_cv;
  std::string health_blob;  // pre-serialized HealthCheckResp (under mu)

  // native lone-request fast path (atomics: set after start, read by the
  // IO thread without s->mu)
  std::atomic<NativeDecideFn> native_fn{nullptr};
  std::atomic<void*> native_kd{nullptr};
  std::atomic<int64_t> native_slow_mask{0};
  std::atomic<long long> native_hits{0};
  // accept method-0 (public GetRateLimits) frames too: only safe while
  // this node owns every key (no routing); re-armed on peer changes
  std::atomic<bool> native_public{false};

  // ---- wire contract v2 ----
  // set before the IO thread starts; >= 2 sends the GREETING on accept
  int wire_v2_max = 1;
  std::atomic<long long> partial_posts{0};  // v2 partial frames streamed
  std::atomic<long long> v2_conns{0};       // conns that upgraded to v2

  // ---- profile (pls_profile; hists and counters under mu) ----
  Hist front_wait;   // parsed -> popped by a puller, per frame
  Hist front_call;   // parsed -> reply written, per frame
  Hist front_parse;  // wire bytes -> columns, per queued frame
  Hist front_write;  // reply serialise + send, per frame
  int64_t pulls = 0, frames_pulled = 0, items_pulled = 0;
  // frames answered in the IO thread never reach the queue:
  // frames_pulled + frames_native is every frame that arrived
  std::atomic<long long> frames_native{0};

  // caller holds mu
  void enqueue(Frame&& f) {
    front_parse.observe(f.parse_ns);
    queue.push_back(std::move(f));
  }
  // caller holds mu; t0 = when serialising the reply began
  void note_reply(int64_t arrive_ns, int64_t t0) {
    const int64_t now = mono_ns();
    front_call.observe(now - arrive_ns);
    front_write.observe(now - t0);
  }
};

bool direct_send(Server* s, Conn* c, const std::string& frame);

// The native-decision core shared by the columnar and gRPC fronts: decide
// a 1-item frame in THIS thread (keydir.cpp decide_one against the row
// mirror). Returns true with out4 = status/limit/remaining/reset filled.
bool native_decide_frame(Server* s, const Frame& f, int64_t out4[4]) {
  NativeDecideFn fn = s->native_fn.load(std::memory_order_acquire);
  if (fn == nullptr || f.count != 1) return false;
  if (f.method != 1 &&
      !(f.method == 0 && s->native_public.load(std::memory_order_relaxed))) {
    return false;
  }
  const int32_t nl = f.name_len[0], ul = f.ukey_len[0];
  if (nl <= 0 || ul <= 0 || nl > 1024 || ul > 1024) return false;
  if ((int64_t)f.behavior[0] &
      s->native_slow_mask.load(std::memory_order_relaxed)) {
    return false;
  }
  char kbuf[2 * 1024 + 1];  // fields are <= 1024 B each (checked above)
  memcpy(kbuf, f.keys.data(), (size_t)nl);
  kbuf[nl] = '_';  // the engine key is name + '_' + unique_key
  memcpy(kbuf + nl + 1, f.keys.data() + nl, (size_t)ul);
  if (!fn(s->native_kd.load(std::memory_order_relaxed), kbuf, nl + 1 + ul,
          f.hits[0], f.limit[0], f.duration[0], (int32_t)f.algorithm[0],
          (int32_t)f.behavior[0], /*now_ms=*/0, out4)) {
    return false;  // cold/invalidated mirror: kernel path + re-seed
  }
  s->native_hits.fetch_add(1, std::memory_order_relaxed);
  s->frames_native.fetch_add(1, std::memory_order_relaxed);
  return true;
}

// Try the native decision for a 1-item method-1 frame. Returns true when
// the reply was written (frame fully served); false = take the queue.
bool try_native_single(Server* s, Conn* c, const Frame& f) {
  int64_t out4[4];
  if (!native_decide_frame(s, f, out4)) return false;
  // 1-item reply frame, written straight from the IO thread
  const uint16_t cnt = 1;
  const uint32_t len = 11 + (4 + 8 + 8 + 8 + 2);
  const int32_t status = (int32_t)out4[0];
  const uint16_t elen = 0;
  std::string frame;
  frame.reserve(4 + len);
  frame.append((const char*)&len, 4);
  frame.append((const char*)&f.rid, 8);
  frame.push_back((char)f.method);
  frame.append((const char*)&cnt, 2);
  frame.append((const char*)&status, 4);
  frame.append((const char*)&out4[1], 8);  // limit
  frame.append((const char*)&out4[2], 8);  // remaining
  frame.append((const char*)&out4[3], 8);  // reset
  frame.append((const char*)&elen, 2);
  std::lock_guard<std::mutex> g(s->mu);
  direct_send(s, c, frame);
  return true;
}

// The v2 GREETING, shaped as a valid v1 reply frame (rid 0 — client rids
// start at 1 — method 0xF0, count 1, version in the status column) so a
// v1 client parses it and drops the unknown rid without error.
std::string greeting_frame() {
  const uint16_t cnt = 1;
  const uint16_t elen = 0;
  const uint32_t len = 11 + (4 + 8 + 8 + 8 + 2);
  const uint64_t rid = 0;
  const int32_t version = 2;
  const int64_t zero = 0;
  std::string frame;
  frame.reserve(4 + len);
  frame.append((const char*)&len, 4);
  frame.append((const char*)&rid, 8);
  frame.push_back((char)kMethodGreeting);
  frame.append((const char*)&cnt, 2);
  frame.append((const char*)&version, 4);
  frame.append((const char*)&zero, 8);
  frame.append((const char*)&zero, 8);
  frame.append((const char*)&zero, 8);
  frame.append((const char*)&elen, 2);
  return frame;
}

void set_nonblock(int fd) {
  int fl = fcntl(fd, F_GETFL, 0);
  fcntl(fd, F_SETFL, fl | O_NONBLOCK);
}

void set_nodelay(int fd) {
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
}

template <typename T>
bool rd(const char*& p, const char* end, T* out) {
  if (p + sizeof(T) > end) return false;
  memcpy(out, p, sizeof(T));
  p += sizeof(T);
  return true;
}

template <typename T>
bool rd_vec(const char*& p, const char* end, std::vector<T>* out, size_t n) {
  if (p + n * sizeof(T) > end) return false;
  out->resize(n);
  memcpy(out->data(), p, n * sizeof(T));
  p += n * sizeof(T);
  return true;
}

// Parse every complete frame in c->inbuf; enqueue under s->mu.
// Returns false on protocol violation (caller closes the conn).
bool drain_inbuf(Server* s, Conn* c) {
  size_t off = 0;
  bool enqueued = false;
  while (true) {
    if (c->inbuf.size() - off < 4) break;
    uint32_t len;
    memcpy(&len, c->inbuf.data() + off, 4);
    if (len < 11 || len > kMaxFrame) return false;
    if (c->inbuf.size() - off - 4 < len) break;
    const char* p = c->inbuf.data() + off + 4;
    const char* end = p + len;
    const int64_t t_parse = mono_ns();
    Frame f;
    f.conn_token = c->token;
    if (!rd(p, end, &f.rid)) return false;
    if (!rd(p, end, &f.method)) return false;
    if (!rd(p, end, &f.count)) return false;
    if ((f.method & 0xF0) == 0xF0) {
      // v2 control frame: HELLO upgrades the conn (count carries the
      // client's max version); unknown control methods skip — forward
      // compatibility, a bad control frame must not kill the conn
      if (f.method == kMethodHello) {
        std::lock_guard<std::mutex> g(s->mu);
        const bool v2 = f.count >= 2 && s->wire_v2_max >= 2;
        if (v2 && c->wire_version < 2)
          s->v2_conns.fetch_add(1, std::memory_order_relaxed);
        c->wire_version = v2 ? 2 : 1;
      }
      off += 4 + len;
      continue;
    }
    // bounds keep one frame always deliverable in a single pull
    // (count <= 1024 < MAX_N, fields <= 1024 B -> ~2 MB = KEY_CAP); a
    // count of 0 is rejected too — it could never complete a reply
    uint16_t count = f.count;
    if (count == 0 || count > 1024) return false;
    if (!rd_vec(p, end, &f.name_len, count)) return false;
    if (!rd_vec(p, end, &f.ukey_len, count)) return false;
    size_t kbytes = 0;
    for (uint16_t i = 0; i < count; i++) {
      if (f.name_len[i] > 1024 || f.ukey_len[i] > 1024) return false;
      kbytes += (size_t)f.name_len[i] + f.ukey_len[i];
    }
    if (p + kbytes > end) return false;
    f.keys.assign(p, kbytes);
    p += kbytes;
    if (!rd_vec(p, end, &f.hits, count)) return false;
    if (!rd_vec(p, end, &f.limit, count)) return false;
    if (!rd_vec(p, end, &f.duration, count)) return false;
    if (!rd_vec(p, end, &f.algorithm, count)) return false;
    if (!rd_vec(p, end, &f.behavior, count)) return false;
    if (p != end) return false;
    off += 4 + len;
    f.arrive_ns = mono_ns();
    f.parse_ns = f.arrive_ns - t_parse;
    if (try_native_single(s, c, f)) continue;  // answered in-thread
    {
      std::lock_guard<std::mutex> g(s->mu);
      PendingReply& pr = c->pending[f.rid];
      pr.arrive_ns = f.arrive_ns;
      pr.method = f.method;
      pr.expected = count;
      pr.got = 0;
      pr.next_seq = 0;  // a reused rid restarts its partial stream
      pr.wire_v2 = c->wire_version >= 2;
      pr.status.assign(count, 0);
      pr.limit.assign(count, 0);
      pr.remaining.assign(count, 0);
      pr.reset.assign(count, 0);
      pr.err.assign(count, std::string());
      pr.meta.assign(count, std::string());
      pr.filled.assign(count, 0);
      s->enqueue(std::move(f));
      enqueued = true;
    }
  }
  if (off) c->inbuf.erase(0, off);
  if (enqueued) s->cv.notify_all();
  return true;
}

void close_conn(Server* s, Conn* c) {
  // extract under s->mu FIRST: pls_send_responses holds s->mu while it
  // touches the conn (incl. a direct send on its fd), so the fd cannot be
  // closed-and-reused under a responder's feet
  std::unique_ptr<Conn> own;
  {
    std::lock_guard<std::mutex> g(s->mu);
    auto it = s->conns.find(c->token);
    if (it == s->conns.end()) return;
    own = std::move(it->second);
    s->conns.erase(it);
  }
  epoll_ctl(s->epoll_fd, EPOLL_CTL_DEL, own->fd, nullptr);
  close(own->fd);
}

void arm(Server* s, Conn* c) {
  epoll_event ev{};
  ev.events = EPOLLIN | (c->want_write ? (uint32_t)EPOLLOUT : 0u);
  ev.data.u64 = c->token;
  epoll_ctl(s->epoll_fd, EPOLL_CTL_MOD, c->fd, &ev);
}

bool flush_out(Server* s, Conn* c) {
  std::lock_guard<std::mutex> g(c->wmu);
  while (!c->outbuf.empty()) {
    ssize_t n = send(c->fd, c->outbuf.data(), c->outbuf.size(), MSG_NOSIGNAL);
    if (n > 0) {
      c->outbuf.erase(0, (size_t)n);
      continue;
    }
    if (n < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
      if (!c->want_write) {
        c->want_write = true;
        arm(s, c);
      }
      return true;
    }
    return false;  // peer went away
  }
  if (c->want_write) {
    c->want_write = false;
    arm(s, c);
  }
  return true;
}

// Responder-thread fast path: write the frame NOW when the socket is
// drained (saves an eventfd->epoll->IO-thread hop per reply); spill the
// remainder to outbuf for the IO thread otherwise. Caller holds s->mu.
// Returns false when the IO thread must be kicked to finish the job.
bool direct_send(Server* s, Conn* c, const std::string& frame) {
  std::lock_guard<std::mutex> g(c->wmu);
  if (c->outbuf.empty()) {
    size_t off = 0;
    while (off < frame.size()) {
      ssize_t n =
          send(c->fd, frame.data() + off, frame.size() - off, MSG_NOSIGNAL);
      if (n > 0) {
        off += (size_t)n;
        continue;
      }
      if (n < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) break;
      return true;  // dead peer: IO thread will notice on its next event
    }
    if (off == frame.size()) return true;
    c->outbuf.append(frame, off, std::string::npos);
  } else {
    c->outbuf += frame;
  }
  if (!c->want_write) {
    c->want_write = true;
    arm(s, c);
  }
  return true;
}

// ------------------------------------------------- HTTP/2 processing

uint32_t be32(const uint8_t* p) {
  return (uint32_t)p[0] << 24 | (uint32_t)p[1] << 16 | (uint32_t)p[2] << 8 |
         p[3];
}

// Trailers-only gRPC error response (grpc spec: HEADERS with END_STREAM
// carrying :status 200 + grpc-status). Not flow-controlled (no DATA).
std::string h2_grpc_error(uint32_t sid, int code, const std::string& msg) {
  std::string hb = h2_resp_headers_block();
  hp_put_literal(&hb, "grpc-status", 11, std::to_string(code));
  if (!msg.empty()) {
    // header values must be visible ASCII: a newline in an exception
    // repr would be a connection-level protocol error at the client
    std::string clean;
    clean.reserve(std::min(msg.size(), (size_t)512));
    for (char ch : msg) {
      if (clean.size() >= 512) break;
      clean.push_back(ch >= 0x20 && ch < 0x7f ? ch : ' ');
    }
    hp_put_literal(&hb, "grpc-message", 12, clean);
  }
  std::string o;
  h2_frame_hdr(&o, (uint32_t)hb.size(), H2_HEADERS,
               H2F_END_HEADERS | H2F_END_STREAM, sid);
  o += hb;
  return o;
}

// Emit DATA frames for payload[off, off+n) split at the peer's max frame
// size, plus the grpc-status trailers after the FINAL byte.
void h2_emit_data(Conn* c, uint32_t sid, const std::string& payload,
                  size_t off, size_t n, std::string* out) {
  const size_t end = off + n;
  while (off < end) {
    const size_t chunk = std::min((size_t)c->max_frame_send, end - off);
    h2_frame_hdr(out, (uint32_t)chunk, H2_DATA, 0, sid);
    out->append(payload, off, chunk);
    off += chunk;
  }
  if (end == payload.size()) {
    std::string tb;
    hp_put_literal(&tb, "grpc-status", 11, "0");
    h2_frame_hdr(out, (uint32_t)tb.size(), H2_HEADERS,
                 H2F_END_HEADERS | H2F_END_STREAM, sid);
    *out += tb;
  }
}

// Full unary gRPC response: HEADERS now; DATA gated on BOTH HTTP/2 flow-
// control windows (connection + per-stream initial budget); trailers after
// the final DATA byte. Whatever the windows cannot carry yet queues on
// c->blocked and drains as the peer's WINDOW_UPDATEs arrive. Appends
// ready-to-send bytes to *acc so a batch of responses coalesces into ONE
// send() per connection. Caller holds s->mu.
void h2_append_response(Server* s, Conn* c, uint32_t sid,
                        const std::string& pb, std::string* acc) {
  (void)s;
  std::string hb = h2_resp_headers_block();
  h2_frame_hdr(acc, (uint32_t)hb.size(), H2_HEADERS, H2F_END_HEADERS, sid);
  *acc += hb;
  std::string payload;
  payload.reserve(5 + pb.size());
  payload.push_back((char)0);  // uncompressed
  payload.push_back((char)(pb.size() >> 24));
  payload.push_back((char)(pb.size() >> 16));
  payload.push_back((char)(pb.size() >> 8));
  payload.push_back((char)pb.size());
  payload += pb;
  int64_t stream_win = c->peer_initial_window;
  auto credit = c->stream_credit.find(sid);
  if (credit != c->stream_credit.end()) {
    stream_win += credit->second;
    c->stream_credit.erase(credit);
  }
  const int64_t can = std::max<int64_t>(
      0, std::min(stream_win, c->send_window));
  const size_t n = std::min((size_t)can, payload.size());
  h2_emit_data(c, sid, payload, 0, n, acc);
  c->send_window -= (int64_t)n;
  if (n < payload.size()) {
    Conn::BlockedResp br;
    br.sid = sid;
    br.payload = std::move(payload);
    br.off = n;
    br.stream_window = stream_win - (int64_t)n;
    c->blocked.push_back(std::move(br));
  }
}

// Drain blocked responses as far as the current windows allow. Caller
// holds s->mu; emitted bytes append to *out.
void h2_flush_blocked(Server* s, Conn* c, std::string* out) {
  (void)s;
  for (auto it = c->blocked.begin(); it != c->blocked.end();) {
    if (c->send_window <= 0) break;
    const size_t rem = it->payload.size() - it->off;
    const int64_t can = std::min(
        (int64_t)rem, std::min(it->stream_window, c->send_window));
    if (can > 0) {
      h2_emit_data(c, it->sid, it->payload, it->off, (size_t)can, out);
      it->off += (size_t)can;
      it->stream_window -= can;
      c->send_window -= can;
    }
    if (it->off == it->payload.size()) {
      c->stream_credit.erase(it->sid);
      it = c->blocked.erase(it);
    } else {
      ++it;
    }
  }
}

void h2_send_response_locked(Server* s, Conn* c, uint32_t sid,
                             const std::string& pb) {
  std::string acc;
  h2_append_response(s, c, sid, pb, &acc);
  if (!acc.empty()) direct_send(s, c, acc);
}

// Native fast path for a parsed 1-item gRPC call: decide in the IO thread
// and write the full H2 response — a lone GetRateLimits RPC never touches
// Python. Mirrors try_native_single's columnar reply.
bool try_native_single_h2(Server* s, Conn* c, uint32_t sid,
                          const Frame& f) {
  int64_t out4[4];
  if (!native_decide_frame(s, f, out4)) return false;
  std::string pb;
  pb_put_resp_item(&pb, (int32_t)out4[0], out4[1], out4[2], out4[3],
                   std::string());
  std::lock_guard<std::mutex> g(s->mu);
  h2_send_response_locked(s, c, sid, pb);
  return true;
}

// Route one complete (headers + body) stream. Returns false only on
// connection-fatal conditions.
bool h2_route_complete(Server* s, Conn* c, uint32_t sid) {
  const int64_t t_parse = mono_ns();  // gRPC framing + protobuf -> columns
  H2Stream st = std::move(c->streams[sid]);
  c->streams.erase(sid);
  const size_t held = st.body.size() + st.hdr_block.size();
  c->buffered_bytes -= std::min(c->buffered_bytes, held);
  // gRPC message framing: 1-byte compressed flag + 4-byte BE length
  std::string msg;
  bool ok_msg = st.body.size() >= 5 && st.body[0] == 0;
  if (ok_msg) {
    const uint32_t mlen = be32((const uint8_t*)st.body.data() + 1);
    ok_msg = (size_t)mlen + 5 == st.body.size();
    if (ok_msg) msg.assign(st.body, 5, mlen);
  }
  if (!ok_msg) {
    const bool compressed = !st.body.empty() && st.body[0] == 1;
    std::lock_guard<std::mutex> g(s->mu);
    direct_send(s, c,
                compressed
                    ? h2_grpc_error(sid, 12, "compression not supported")
                    : h2_grpc_error(sid, 13, "malformed grpc framing"));
    return true;
  }
  int method = -1;
  if (st.path == "/pb.gubernator.V1/GetRateLimits") {
    method = 0;
  } else if (st.path == "/pb.gubernator.PeersV1/GetPeerRateLimits") {
    method = 1;
  } else if (st.path == "/pb.gubernator.V1/HealthCheck") {
    bool served = false;
    {
      std::lock_guard<std::mutex> g(s->mu);
      if (!s->health_blob.empty()) {
        h2_send_response_locked(s, c, sid, s->health_blob);
        served = true;
      } else {
        s->raw_queue.push_back({c->token, sid, st.path, std::move(msg)});
      }
    }
    if (!served) s->raw_cv.notify_one();
    return true;
  } else {
    // UpdatePeerGlobals and anything else: Python answers from the full
    // pb bytes (unknown methods get UNIMPLEMENTED there)
    {
      std::lock_guard<std::mutex> g(s->mu);
      s->raw_queue.push_back({c->token, sid, st.path, std::move(msg)});
    }
    s->raw_cv.notify_one();
    return true;
  }
  Frame f;
  f.conn_token = c->token;
  f.rid = sid;
  f.method = (uint8_t)method;
  const int pr = pb_parse_get_rate_limits(
      (const uint8_t*)msg.data(), (const uint8_t*)msg.data() + msg.size(),
      &f);
  if (pr < 0) {
    std::lock_guard<std::mutex> g(s->mu);
    direct_send(s, c, h2_grpc_error(sid, 13, "malformed protobuf"));
    return true;
  }
  if (pr == 0) {  // fields the fast parser doesn't know: Python decides
    {
      std::lock_guard<std::mutex> g(s->mu);
      s->raw_queue.push_back({c->token, sid, st.path, std::move(msg)});
    }
    s->raw_cv.notify_one();
    return true;
  }
  f.arrive_ns = mono_ns();
  f.parse_ns = f.arrive_ns - t_parse;
  if (try_native_single_h2(s, c, sid, f)) return true;
  {
    std::lock_guard<std::mutex> g(s->mu);
    PendingReply& rep = c->pending[f.rid];
    rep.arrive_ns = f.arrive_ns;
    rep.method = f.method;
    rep.h2_stream = sid;
    rep.expected = f.count;
    rep.got = 0;
    rep.next_seq = 0;
    rep.wire_v2 = false;  // H2 replies always leave whole
    rep.status.assign(f.count, 0);
    rep.limit.assign(f.count, 0);
    rep.remaining.assign(f.count, 0);
    rep.reset.assign(f.count, 0);
    rep.err.assign(f.count, std::string());
    rep.meta.assign(f.count, std::string());
    rep.filled.assign(f.count, 0);
    s->enqueue(std::move(f));
  }
  s->cv.notify_all();
  return true;
}

// Parse every complete HTTP/2 frame in c->inbuf (the gRPC-front analogue
// of drain_inbuf). Returns false on protocol violation (conn closes).
bool h2_drain(Server* s, Conn* c) {
  size_t off = 0;
  if (!c->preface_ok) {
    if (c->inbuf.size() < kH2PrefaceLen) return true;
    if (memcmp(c->inbuf.data(), kH2Preface, kH2PrefaceLen) != 0)
      return false;
    off = kH2PrefaceLen;
    c->preface_ok = true;
    std::string o;
    // our SETTINGS: 4 MB initial stream window (no per-stream stalls for
    // bodies up to the 4 MB cap) + a concurrent-stream cap (enforced in
    // the HEADERS handler: the port is public and unauthenticated)
    h2_frame_hdr(&o, 12, H2_SETTINGS, 0, 0);
    const uint16_t id4 = htons(4);
    o.append((const char*)&id4, 2);
    const uint32_t iw = htonl(4u << 20);
    o.append((const char*)&iw, 4);
    const uint16_t id3 = htons(3);
    o.append((const char*)&id3, 2);
    const uint32_t mcs = htonl(kH2MaxStreams);
    o.append((const char*)&mcs, 4);
    // plus a large connection window so ingest is never throttled
    h2_frame_hdr(&o, 4, H2_WINDOW_UPDATE, 0, 0);
    const uint32_t inc = htonl(0x3fff0000);
    o.append((const char*)&inc, 4);
    std::lock_guard<std::mutex> g(s->mu);
    direct_send(s, c, o);
  }
  while (true) {
    if (c->inbuf.size() - off < 9) break;
    const uint8_t* h = (const uint8_t*)c->inbuf.data() + off;
    const uint32_t len =
        (uint32_t)h[0] << 16 | (uint32_t)h[1] << 8 | h[2];
    const uint8_t type = h[3], flags = h[4];
    const uint32_t sid = be32(h + 5) & 0x7fffffff;
    if (len > (1u << 20)) return false;  // far past our max frame size
    if (c->inbuf.size() - off - 9 < len) break;
    const uint8_t* p = h + 9;
    const uint8_t* pe = p + len;
    if (c->cont_stream && type != H2_CONTINUATION) return false;
    switch (type) {
      case H2_SETTINGS: {
        if (sid != 0 || len % 6 != 0) return false;
        if (flags & H2F_ACK) break;
        {
          // responder threads read these under s->mu (h2_append_response)
          std::lock_guard<std::mutex> g(s->mu);
          for (const uint8_t* q = p; q + 6 <= pe; q += 6) {
            const uint16_t id = (uint16_t)(q[0] << 8 | q[1]);
            const uint32_t val = be32(q + 2);
            if (id == 5) {  // SETTINGS_MAX_FRAME_SIZE
              if (val >= 16384 && val <= 16777215) c->max_frame_send = val;
            } else if (id == 4) {  // SETTINGS_INITIAL_WINDOW_SIZE
              if (val <= 0x7fffffff) {
                const int64_t delta =
                    (int64_t)val - c->peer_initial_window;
                c->peer_initial_window = (int64_t)val;
                // RFC 7540 §6.9.2: adjust every in-flight stream budget
                for (auto& br : c->blocked) br.stream_window += delta;
              }
            }
          }
        }
        std::string o;
        h2_frame_hdr(&o, 0, H2_SETTINGS, H2F_ACK, 0);
        std::lock_guard<std::mutex> g(s->mu);
        direct_send(s, c, o);
        break;
      }
      case H2_PING: {
        if (len != 8 || sid != 0) return false;
        if (flags & H2F_ACK) break;
        std::string o;
        h2_frame_hdr(&o, 8, H2_PING, H2F_ACK, 0);
        o.append((const char*)p, 8);
        std::lock_guard<std::mutex> g(s->mu);
        direct_send(s, c, o);
        break;
      }
      case H2_WINDOW_UPDATE: {
        if (len != 4) return false;
        const uint32_t inc = be32(p) & 0x7fffffff;
        if (inc) {
          std::lock_guard<std::mutex> g(s->mu);
          std::string out;
          if (sid == 0) {
            c->send_window += inc;
          } else {
            bool found = false;
            for (auto& br : c->blocked) {
              if (br.sid == sid) {
                br.stream_window += inc;
                found = true;
                break;
              }
            }
            if (!found && c->stream_credit.size() < 4 * kH2MaxStreams) {
              c->stream_credit[sid] += inc;  // response not built yet
            }
          }
          h2_flush_blocked(s, c, &out);
          if (!out.empty()) direct_send(s, c, out);
        }
        break;
      }
      case H2_HEADERS: {
        if (sid == 0 || (sid & 1) == 0) return false;
        const uint8_t* q = p;
        uint8_t pad = 0;
        if (flags & H2F_PADDED) {
          if (q >= pe) return false;
          pad = *q++;
        }
        if (flags & H2F_PRIORITY) {
          if (pe - q < 5) return false;
          q += 5;
        }
        if (pe - q < pad) return false;
        if (c->streams.find(sid) == c->streams.end() &&
            c->streams.size() >= kH2MaxStreams) {
          return false;  // stream flood on the public port
        }
        H2Stream& st = c->streams[sid];
        const size_t add_h = (size_t)(pe - pad - q);
        st.hdr_block.append((const char*)q, add_h);
        c->buffered_bytes += add_h;
        if (c->buffered_bytes > kH2MaxBuffered) return false;
        if (flags & H2F_END_STREAM) st.end_stream = true;
        if (flags & H2F_END_HEADERS) {
          if (!hpack_decode_block(&c->hpack, st.hdr_block, &st.path))
            return false;
          st.hdr_block.clear();
          st.hdr_end = true;
          if (st.end_stream && !h2_route_complete(s, c, sid)) return false;
        } else {
          c->cont_stream = sid;
        }
        break;
      }
      case H2_CONTINUATION: {
        if (sid == 0 || sid != c->cont_stream) return false;
        auto it = c->streams.find(sid);
        if (it == c->streams.end()) return false;
        H2Stream& st = it->second;
        st.hdr_block.append((const char*)p, len);
        c->buffered_bytes += len;
        if (st.hdr_block.size() > (64u << 10) ||
            c->buffered_bytes > kH2MaxBuffered) {
          return false;
        }
        if (flags & H2F_END_HEADERS) {
          c->cont_stream = 0;
          if (!hpack_decode_block(&c->hpack, st.hdr_block, &st.path))
            return false;
          st.hdr_block.clear();
          st.hdr_end = true;
          if (st.end_stream && !h2_route_complete(s, c, sid)) return false;
        }
        break;
      }
      case H2_DATA: {
        if (sid == 0) return false;
        const uint8_t* q = p;
        uint8_t pad = 0;
        if (flags & H2F_PADDED) {
          if (q >= pe) return false;
          pad = *q++;
        }
        if (pe - q < pad) return false;
        auto it = c->streams.find(sid);
        if (it != c->streams.end()) {
          H2Stream& st = it->second;
          const size_t add_b = (size_t)(pe - pad - q);
          st.body.append((const char*)q, add_b);
          c->buffered_bytes += add_b;
          if (st.body.size() > kMaxH2Body ||
              c->buffered_bytes > kH2MaxBuffered) {
            return false;
          }
          if (flags & H2F_END_STREAM) {
            st.end_stream = true;
            if (st.hdr_end && !h2_route_complete(s, c, sid)) return false;
          }
        }
        // flow-control credit for consumed bytes (connection level; the
        // 4 MB initial stream window covers per-stream budgets)
        if (len) {
          std::string o;
          h2_frame_hdr(&o, 4, H2_WINDOW_UPDATE, 0, 0);
          const uint32_t credit = htonl(len);
          o.append((const char*)&credit, 4);
          std::lock_guard<std::mutex> g(s->mu);
          direct_send(s, c, o);
        }
        break;
      }
      case H2_RST_STREAM: {
        if (len != 4 || sid == 0) return false;
        {
          auto sit = c->streams.find(sid);
          if (sit != c->streams.end()) {
            const size_t held = sit->second.body.size() +
                                sit->second.hdr_block.size();
            c->buffered_bytes -= std::min(c->buffered_bytes, held);
            c->streams.erase(sit);
          }
        }
        std::lock_guard<std::mutex> g(s->mu);
        c->pending.erase((uint64_t)sid);  // drop late worker replies
        c->stream_credit.erase(sid);
        for (auto it2 = c->blocked.begin(); it2 != c->blocked.end();) {
          if (it2->sid == sid) {
            it2 = c->blocked.erase(it2);  // cancelled: free the payload
          } else {
            ++it2;
          }
        }
        break;
      }
      case H2_GOAWAY:
      default:
        break;  // PRIORITY / unknown frame types: skip
    }
    off += 9 + len;
  }
  if (off) c->inbuf.erase(0, off);
  return true;
}

// Serialize a completed pending reply (v1 whole-frame or gRPC/H2) into
// *out and erase the pending entry. Caller holds s->mu and has verified
// pr.got == pr.expected. Shared by pls_send_responses and the v1/H2
// accumulate path of pls_send_partial so both emit identical bytes.
void finish_pending(Server* s, Conn* c,
                    std::map<uint64_t, PendingReply>::iterator pit,
                    std::string* out) {
  PendingReply& pr = pit->second;
  if (pr.h2_stream) {
    // gRPC/H2 connection: serialize the pb response and send
    std::string pb;
    for (int j2 = 0; j2 < pr.expected; j2++) {
      pb_put_resp_item(&pb, pr.status[j2], pr.limit[j2], pr.remaining[j2],
                       pr.reset[j2], pr.err[j2], pr.meta[j2]);
    }
    const uint32_t sid2 = pr.h2_stream;
    c->pending.erase(pit);
    h2_append_response(s, c, sid2, pb, out);
    return;
  }
  uint16_t cnt = pr.expected;
  size_t ebytes = 0;
  for (auto& e : pr.err) ebytes += e.size();
  uint32_t len = 11 + cnt * (4 + 8 + 8 + 8 + 2) + (uint32_t)ebytes;
  std::string frame;
  frame.reserve(4 + len);
  frame.append((const char*)&len, 4);
  uint64_t r = pit->first;
  frame.append((const char*)&r, 8);
  frame.push_back((char)pr.method);
  frame.append((const char*)&cnt, 2);
  frame.append((const char*)pr.status.data(), cnt * 4);
  frame.append((const char*)pr.limit.data(), cnt * 8);
  frame.append((const char*)pr.remaining.data(), cnt * 8);
  frame.append((const char*)pr.reset.data(), cnt * 8);
  for (auto& e : pr.err) {
    uint16_t el = (uint16_t)e.size();
    frame.append((const char*)&el, 2);
  }
  for (auto& e : pr.err) frame += e;
  c->pending.erase(pit);
  *out += frame;
}

void io_loop(Server* s) {
  epoll_event evs[64];
  while (true) {
    int n = epoll_wait(s->epoll_fd, evs, 64, 100);
    {
      std::lock_guard<std::mutex> g(s->mu);
      if (s->stopping) return;
    }
    for (int i = 0; i < n; i++) {
      uint64_t token = evs[i].data.u64;
      if (token == 0 || token == 1) {  // columnar / grpc listener
        const int lfd = token == 0 ? s->listen_fd : s->grpc_listen_fd;
        while (true) {
          int fd = accept(lfd, nullptr, nullptr);
          if (fd < 0) break;
          set_nonblock(fd);
          set_nodelay(fd);
          auto c = std::make_unique<Conn>();
          c->fd = fd;
          c->h2 = token == 1;
          {
            std::lock_guard<std::mutex> g(s->mu);
            c->token = s->next_token++;
            epoll_event ev{};
            ev.events = EPOLLIN;
            ev.data.u64 = c->token;
            epoll_ctl(s->epoll_fd, EPOLL_CTL_ADD, fd, &ev);
            Conn* cp = c.get();
            s->conns[cp->token] = std::move(c);
            // server speaks first: v2-capable columnar conns get the
            // GREETING; a v1 client parses-and-drops it (rid 0)
            if (!cp->h2 && s->wire_v2_max >= 2)
              direct_send(s, cp, greeting_frame());
          }
        }
        continue;
      }
      if (token == UINT64_MAX) {  // wake_fd: outbox handled above
        uint64_t junk;
        (void)read(s->wake_fd, &junk, 8);
        continue;
      }
      Conn* c = nullptr;
      {
        std::lock_guard<std::mutex> g(s->mu);
        auto it = s->conns.find(token);
        if (it != s->conns.end()) c = it->second.get();
      }
      if (!c) continue;
      bool dead = false;
      if (evs[i].events & (EPOLLHUP | EPOLLERR)) dead = true;
      if (!dead && (evs[i].events & EPOLLIN)) {
        char buf[65536];
        while (true) {
          ssize_t r = recv(c->fd, buf, sizeof(buf), 0);
          if (r > 0) {
            c->inbuf.append(buf, (size_t)r);
            if (c->inbuf.size() > 2 * kMaxFrame) {
              dead = true;
              break;
            }
            continue;
          }
          if (r == 0) dead = true;
          else if (errno != EAGAIN && errno != EWOULDBLOCK) dead = true;
          break;
        }
        if (!dead && !(c->h2 ? h2_drain(s, c) : drain_inbuf(s, c)))
          dead = true;
      }
      if (!dead && (evs[i].events & EPOLLOUT)) {
        if (!flush_out(s, c)) dead = true;
      }
      if (dead) close_conn(s, c);
    }
  }
}

}  // namespace

extern "C" {

// Start a listener on INADDR_ANY:port (port 0 picks one) — peers reach it
// from other hosts, which the cross-host topology requires. Like the
// reference's peer gRPC surface it is UNAUTHENTICATED (peers.proto served
// insecure); deploy it on the peer network only, or set
// GUBER_PEER_LINK_OFFSET=0 to disable and keep every peer call on gRPC.
// Returns an opaque handle, or 0 on failure; *bound_port gets the port.
// wire_v2_max caps the negotiable wire contract: >= 2 turns on the
// GREETING/HELLO upgrade (the daemon's value), 1 keeps the server
// byte-exact v1 — it never greets and ignores HELLOs (the interop tests'
// old binary).
void* pls_start2(int port, int* bound_port, int wire_v2_max) {
  auto s = std::make_unique<Server>();
  s->wire_v2_max = wire_v2_max;
  s->listen_fd = socket(AF_INET, SOCK_STREAM, 0);
  if (s->listen_fd < 0) return nullptr;
  int one = 1;
  setsockopt(s->listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_ANY);
  addr.sin_port = htons((uint16_t)port);
  if (bind(s->listen_fd, (sockaddr*)&addr, sizeof(addr)) < 0 ||
      listen(s->listen_fd, 1024) < 0) {
    close(s->listen_fd);
    return nullptr;
  }
  socklen_t alen = sizeof(addr);
  getsockname(s->listen_fd, (sockaddr*)&addr, &alen);
  s->port = ntohs(addr.sin_port);
  if (bound_port) *bound_port = s->port;
  set_nonblock(s->listen_fd);
  s->epoll_fd = epoll_create1(0);
  s->wake_fd = eventfd(0, EFD_NONBLOCK);
  epoll_event ev{};
  ev.events = EPOLLIN;
  ev.data.u64 = 0;  // listener sentinel
  epoll_ctl(s->epoll_fd, EPOLL_CTL_ADD, s->listen_fd, &ev);
  epoll_event wev{};
  wev.events = EPOLLIN;
  wev.data.u64 = UINT64_MAX;  // wake sentinel
  epoll_ctl(s->epoll_fd, EPOLL_CTL_ADD, s->wake_fd, &wev);
  Server* raw = s.release();
  raw->io = std::thread(io_loop, raw);
  return raw;
}

// Legacy 2-arg ABI, kept so out-of-tree callers (tsan harness scripts)
// stay valid: a v1-only server, bit-identical to the pre-v2 contract.
void* pls_start(int port, int* bound_port) {
  return pls_start2(port, bound_port, 1);
}

// Stop the IO thread and wake every blocked puller (they return -1).
// Does NOT free: callers must join their worker threads first, then call
// pls_free — a puller inside pls_next_batch must never race the delete.
void pls_stop(void* h) {
  auto* s = (Server*)h;
  {
    std::lock_guard<std::mutex> g(s->mu);
    s->stopping = true;
  }
  uint64_t one = 1;
  (void)write(s->wake_fd, &one, 8);
  s->cv.notify_all();
  s->raw_cv.notify_all();
  s->io.join();
}

void pls_free(void* h) {
  auto* s = (Server*)h;
  for (auto& [tok, c] : s->conns) close(c->fd);
  close(s->listen_fd);
  if (s->grpc_listen_fd >= 0) close(s->grpc_listen_fd);
  close(s->epoll_fd);
  close(s->wake_fd);
  delete s;
}

// Open the gRPC/HTTP/2 listener on host:port (0 picks a port; host NULL
// or "" binds every interface) and register it with the running IO loop.
// Returns the bound port, -1 on failure. Wire-compatible with the
// reference's public+peers gRPC surface; methods the C tier cannot serve
// verbatim are pulled by Python via pls_next_raw.
int pls_start_grpc(void* h, int port, const char* host) {
  auto* s = (Server*)h;
  const int fd = socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  int one = 1;
  setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_ANY);
  if (host != nullptr && host[0] != 0 &&
      strcmp(host, "0.0.0.0") != 0) {
    if (inet_pton(AF_INET, host, &addr.sin_addr) != 1) {
      close(fd);
      return -1;  // GUBER_GRPC_ADDRESS host must be an IPv4 literal here
    }
  }
  addr.sin_port = htons((uint16_t)port);
  if (bind(fd, (sockaddr*)&addr, sizeof(addr)) < 0 ||
      listen(fd, 1024) < 0) {
    close(fd);
    return -1;
  }
  socklen_t alen = sizeof(addr);
  getsockname(fd, (sockaddr*)&addr, &alen);
  set_nonblock(fd);
  {
    std::lock_guard<std::mutex> g(s->mu);
    s->grpc_listen_fd = fd;
    s->grpc_port = ntohs(addr.sin_port);
  }
  epoll_event ev{};
  ev.events = EPOLLIN;
  ev.data.u64 = 1;  // grpc listener sentinel
  epoll_ctl(s->epoll_fd, EPOLL_CTL_ADD, fd, &ev);
  return s->grpc_port;
}

// Publish the pre-serialized HealthCheckResp the IO thread answers
// /pb.gubernator.V1/HealthCheck with (len 0 reverts to the Python path).
void pls_set_health(void* h, const char* blob, int len) {
  auto* s = (Server*)h;
  std::lock_guard<std::mutex> g(s->mu);
  s->health_blob.assign(blob, (size_t)(len < 0 ? 0 : len));
}

// Pull one punted gRPC call (blocking; call via CDLL so the GIL drops).
// Returns the body length (>= 0), -1 when stopping, -3 on timeout, -2
// when a buffer is too small (the call is dropped with an error reply).
int pls_next_raw(void* h, long long timeout_us, char* path, int path_cap,
                 int* path_len, char* body, int body_cap,
                 unsigned long long* conn_token, unsigned int* stream_id) {
  auto* s = (Server*)h;
  std::unique_lock<std::mutex> g(s->mu);
  if (s->raw_queue.empty()) {
    s->raw_cv.wait_for(g, std::chrono::microseconds(timeout_us), [&] {
      return !s->raw_queue.empty() || s->stopping;
    });
  }
  if (s->stopping) return -1;
  if (s->raw_queue.empty()) return -3;
  Server::RawReq r = std::move(s->raw_queue.front());
  s->raw_queue.pop_front();
  if ((int)r.path.size() > path_cap || (int)r.body.size() > body_cap) {
    auto cit = s->conns.find(r.conn_token);
    if (cit != s->conns.end()) {
      direct_send(s, cit->second.get(),
                  h2_grpc_error(r.stream_id, 8, "request too large"));
    }
    return -2;
  }
  memcpy(path, r.path.data(), r.path.size());
  *path_len = (int)r.path.size();
  memcpy(body, r.body.data(), r.body.size());
  *conn_token = r.conn_token;
  *stream_id = r.stream_id;
  return (int)r.body.size();
}

// Answer a punted call: grpc_status 0 sends `resp` as the unary response
// body; nonzero sends a trailers-only error with `grpc_msg`.
void pls_send_raw(void* h, unsigned long long conn_token,
                  unsigned int stream_id, const char* resp, int len,
                  int grpc_status, const char* grpc_msg) {
  auto* s = (Server*)h;
  std::lock_guard<std::mutex> g(s->mu);
  auto cit = s->conns.find(conn_token);
  if (cit == s->conns.end()) return;  // client vanished
  Conn* c = cit->second.get();
  if (grpc_status != 0) {
    direct_send(s, c,
                h2_grpc_error(stream_id, grpc_status,
                              grpc_msg ? grpc_msg : ""));
    return;
  }
  h2_send_response_locked(s, c, stream_id,
                          std::string(resp, (size_t)(len < 0 ? 0 : len)));
}

int pls_grpc_port(void* h) { return ((Server*)h)->grpc_port; }

// Pull everything pending (up to max_n items) into caller buffers. Blocks
// up to timeout_us when the queue is empty (call via CDLL: GIL released).
// Returns the item count, 0 on timeout, -1 when stopping.
// Buffers: keys (name+unique_key concatenated per item; cap key_cap) with
// key_off[n+1] entry bounds and name_len[n] split points; i64
// hits/limit/duration; i32 algorithm/behavior/method/idx; u64
// conn_token/rid — all length max_n.
int pls_next_batch(void* h, long long timeout_us, char* keys, int key_cap,
                   int* key_off, int* name_len, long long* hits,
                   long long* limit, long long* duration, int* algorithm,
                   int* behavior, int* method, int* idx,
                   unsigned long long* conn_token, unsigned long long* rid,
                   int max_n) {
  auto* s = (Server*)h;
  std::unique_lock<std::mutex> g(s->mu);
  if (s->queue.empty()) {
    s->cv.wait_for(g, std::chrono::microseconds(timeout_us),
                   [&] { return !s->queue.empty() || s->stopping; });
  }
  if (s->stopping) return -1;
  int n = 0, koff = 0;
  key_off[0] = 0;
  const int64_t now = s->queue.empty() ? 0 : mono_ns();
  while (!s->queue.empty()) {
    Frame& f = s->queue.front();
    int count = f.count;
    if (n + count > max_n) break;
    if (koff + (int)f.keys.size() > key_cap) break;
    s->front_wait.observe(now - f.arrive_ns);
    s->frames_pulled++;
    // columnar frame -> columnar caller buffers: bulk copies
    memcpy(keys + koff, f.keys.data(), f.keys.size());
    for (int i = 0; i < count; i++) {
      koff += (int)f.name_len[i] + (int)f.ukey_len[i];
      key_off[n + i + 1] = koff;
      name_len[n + i] = (int)f.name_len[i];
      algorithm[n + i] = (int)f.algorithm[i];
      behavior[n + i] = (int)f.behavior[i];
      method[n + i] = (int)f.method;
      idx[n + i] = i;
      conn_token[n + i] = f.conn_token;
      rid[n + i] = f.rid;
    }
    memcpy(hits + n, f.hits.data(), count * 8);
    memcpy(limit + n, f.limit.data(), count * 8);
    memcpy(duration + n, f.duration.data(), count * 8);
    n += count;
    s->queue.pop_front();
    if (n == max_n) break;
  }
  if (n) {
    s->pulls++;
    s->items_pulled += n;
  }
  return n;
}

// Hand back n reply items (same tag arrays as pls_next_batch). Items of a
// rid may arrive across multiple calls; a frame is written once complete.
void pls_send_responses(void* h, int n, const unsigned long long* conn_token,
                        const unsigned long long* rid, const int* idx,
                        const int* status, const long long* limit,
                        const long long* remaining, const long long* reset,
                        const int* err_off, const char* err_buf,
                        const int* meta_off, const char* meta_buf) {
  auto* s = (Server*)h;
  std::lock_guard<std::mutex> g(s->mu);
  // coalesce: all of this call's completed replies to one conn leave in
  // ONE send() (a 100-wide herd pays 1 syscall per conn, not 100)
  std::map<Conn*, std::string> acc;
  std::vector<int64_t> done;  // arrive_ns of the rids this call completes
  int64_t t0 = 0;             // the first of them starts to serialise
  for (int i = 0; i < n; i++) {
    auto cit = s->conns.find(conn_token[i]);
    if (cit == s->conns.end()) continue;  // client vanished
    Conn* c = cit->second.get();
    auto pit = c->pending.find(rid[i]);
    if (pit == c->pending.end()) continue;
    PendingReply& pr = pit->second;
    int j = idx[i];
      if (j < 0 || j >= pr.expected) continue;
    if (!pr.filled[j]) pr.got++;
    pr.filled[j] = 1;
    pr.status[j] = status[i];
    pr.limit[j] = limit[i];
    pr.remaining[j] = remaining[i];
    pr.reset[j] = reset[i];
    int elen = err_off[i + 1] - err_off[i];
    pr.err[j].assign(err_buf + err_off[i], (size_t)elen);
    if (meta_off != nullptr) {
      const int mlen = meta_off[i + 1] - meta_off[i];
      pr.meta[j].assign(meta_buf + meta_off[i], (size_t)mlen);
    }
    if (pr.got == pr.expected) {
      if (done.empty()) t0 = mono_ns();
      done.push_back(pr.arrive_ns);
      finish_pending(s, c, pit, &acc[c]);
    }
  }
  for (auto& [c, bytes] : acc) {
    if (!bytes.empty()) direct_send(s, c, bytes);
  }
  if (!done.empty()) {
    // the replies left in coalesced sends: each frame gets an equal part
    // of the call's serialise + write time
    const int64_t now = mono_ns();
    const int64_t each = (now - t0) / (int64_t)done.size();
    for (int64_t arrive : done) {
      s->front_call.observe(now - arrive);
      s->front_write.observe(each);
    }
  }
}

// Post one contiguous row-span [base, base+n) of a rid's reply (wire
// contract v2). On a negotiated-v2 columnar conn the span streams NOW as
// a seq-numbered 0xF2 partial frame — per-rid seq order, cross-rid
// interleaving free — and the pending entry is erased when the final
// span posts. On a v1 conn or a gRPC/H2 stream the rows accumulate into
// the pending entry and the reply leaves whole once complete, exactly as
// pls_send_responses would send it: callers never branch on the peer's
// version. err_off/meta_off are span-relative (n+1 entries); meta_off
// may be null when no H2 metadata rides along.
void pls_send_partial(void* h, unsigned long long conn_token,
                      unsigned long long rid, int base, int n,
                      const int* status, const long long* limit,
                      const long long* remaining, const long long* reset,
                      const int* err_off, const char* err_buf,
                      const int* meta_off, const char* meta_buf) {
  auto* s = (Server*)h;
  std::lock_guard<std::mutex> g(s->mu);
  auto cit = s->conns.find(conn_token);
  if (cit == s->conns.end()) return;  // client vanished
  Conn* c = cit->second.get();
  auto pit = c->pending.find(rid);
  if (pit == c->pending.end()) return;  // already final (or raced close)
  PendingReply& pr = pit->second;
  if (base < 0 || n <= 0 || base + n > (int)pr.expected) return;
  if (pr.wire_v2 && pr.h2_stream == 0) {
    int fresh = 0;
    for (int k = 0; k < n; k++) {
      if (!pr.filled[base + k]) {
        pr.filled[base + k] = 1;
        pr.got++;
        fresh++;
      }
    }
    if (fresh == 0) return;  // span already streamed
    const int64_t t0 = mono_ns();
    const int64_t arrive = pr.arrive_ns;
    const uint16_t cnt = (uint16_t)n;
    const uint16_t seq = pr.next_seq++;
    const uint16_t b16 = (uint16_t)base;
    const uint8_t fin = pr.got == pr.expected ? 1 : 0;
    const size_t ebytes = (size_t)(err_off[n] - err_off[0]);
    const uint32_t len =
        11 + 5 + cnt * (4 + 8 + 8 + 8 + 2) + (uint32_t)ebytes;
    std::string frame;
    frame.reserve(4 + len);
    frame.append((const char*)&len, 4);
    uint64_t r = rid;
    frame.append((const char*)&r, 8);
    frame.push_back((char)kMethodPartial);
    frame.append((const char*)&cnt, 2);
    frame.append((const char*)&seq, 2);
    frame.append((const char*)&b16, 2);
    frame.push_back((char)fin);
    frame.append((const char*)status, cnt * 4);
    frame.append((const char*)limit, cnt * 8);
    frame.append((const char*)remaining, cnt * 8);
    frame.append((const char*)reset, cnt * 8);
    for (int k = 0; k < n; k++) {
      const uint16_t el = (uint16_t)(err_off[k + 1] - err_off[k]);
      frame.append((const char*)&el, 2);
    }
    if (ebytes) frame.append(err_buf + err_off[0], ebytes);
    if (fin) c->pending.erase(pit);
    direct_send(s, c, frame);
    if (fin) s->note_reply(arrive, t0);
    s->partial_posts.fetch_add(1, std::memory_order_relaxed);
    return;
  }
  // v1 / H2 destination: accumulate; the reply leaves whole when full
  for (int k = 0; k < n; k++) {
    const int j = base + k;
    if (!pr.filled[j]) pr.got++;
    pr.filled[j] = 1;
    pr.status[j] = status[k];
    pr.limit[j] = limit[k];
    pr.remaining[j] = remaining[k];
    pr.reset[j] = reset[k];
    pr.err[j].assign(err_buf + err_off[k],
                     (size_t)(err_off[k + 1] - err_off[k]));
    if (meta_off != nullptr) {
      pr.meta[j].assign(meta_buf + meta_off[k],
                        (size_t)(meta_off[k + 1] - meta_off[k]));
    }
  }
  if (pr.got == pr.expected) {
    const int64_t t0 = mono_ns();
    const int64_t arrive = pr.arrive_ns;
    std::string out;
    finish_pending(s, c, pit, &out);
    if (!out.empty()) direct_send(s, c, out);
    s->note_reply(arrive, t0);
  }
}

// Live reply-assembly entries across every conn: the leak probe the
// wire-v2 tests assert on after disconnect/teardown.
long long pls_pending_count(void* h) {
  auto* s = (Server*)h;
  std::lock_guard<std::mutex> g(s->mu);
  long long total = 0;
  for (auto& [tok, c] : s->conns) total += (long long)c->pending.size();
  return total;
}

long long pls_partial_posts(void* h) {
  return ((Server*)h)->partial_posts.load(std::memory_order_relaxed);
}

long long pls_v2_conns(void* h) {
  return ((Server*)h)->v2_conns.load(std::memory_order_relaxed);
}

int pls_port(void* h) { return ((Server*)h)->port; }

// Copy the front's profile into out[0..kProfileLen): four histograms in the
// order front_wait, front_call, front_parse, front_write, each kHistBuckets
// counts then n, total_ns, max_ns; then pulls, frames_pulled, items_pulled,
// frames_native. Called at scrape time only. Returns the values written,
// -1 when cap is too small.
int pls_profile(void* h, long long* out, int cap) {
  constexpr int kProfileLen = 4 * (kHistBuckets + 3) + 4;
  if (cap < kProfileLen) return -1;
  auto* s = (Server*)h;
  std::lock_guard<std::mutex> g(s->mu);
  int k = 0;
  for (const Hist* hist : {&s->front_wait, &s->front_call, &s->front_parse,
                           &s->front_write}) {
    for (int i = 0; i < kHistBuckets; i++) out[k++] = hist->counts[i];
    out[k++] = hist->n;
    out[k++] = hist->total_ns;
    out[k++] = hist->max_ns;
  }
  out[k++] = s->pulls;
  out[k++] = s->frames_pulled;
  out[k++] = s->items_pulled;
  out[k++] = s->frames_native.load(std::memory_order_relaxed);
  return k;
}

// Enable the native lone-request fast path: `fn` is keydir_decide_one's
// address, `kd` the engine's KeyDir handle, `slow_mask` the behavior bits
// that must take the Python path (gregorian, GLOBAL, MULTI_REGION).
void pls_set_native(void* h, void* fn, void* kd, long long slow_mask) {
  auto* s = (Server*)h;
  s->native_kd.store(kd, std::memory_order_relaxed);
  s->native_slow_mask.store(slow_mask, std::memory_order_relaxed);
  s->native_fn.store((NativeDecideFn)fn, std::memory_order_release);
}

long long pls_native_hits(void* h) {
  return ((Server*)h)->native_hits.load(std::memory_order_relaxed);
}

// Toggle IO-thread decisions for method-0 (public) lone frames — only
// while the node owns every key (standalone); peer changes re-arm it.
void pls_set_native_public(void* h, int on) {
  ((Server*)h)->native_public.store(on != 0, std::memory_order_relaxed);
}

}  // extern "C"
