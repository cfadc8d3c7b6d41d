// Native device-owner gRPC ext_authz frontend.
//
// The reference serves Check() from a Go gRPC server in the same process as
// the evaluation hot loop (ref: main.go:437-488, pkg/service/auth.go:239-310).
// The TPU-era equivalent must keep ONE process owning the chip (TPUs are
// process-exclusive) while the wire path runs at native speed: this file is
// an epoll HTTP/2 gRPC server (its own framer and HPACK decoder, built for
// unary calls: "HTTP/2" below) that parses CheckRequest protobufs where recv
// put them, encodes pattern-only ("fast lane") requests straight into the
// packed kernel operands, micro-batches them, and hands each batch to the
// Python device-owner thread for ONE JAX dispatch.  The per-request Python
// cost of the asyncio engine loop (~45µs) drops to zero; Python is touched
// once per batch.
//
// Correctness contract:
//   - fast lane only for configs whose full pipeline semantics reduce to
//     the compiled kernel verdict (anonymous identity + compiled pattern
//     authorization + static responses) — eligibility decided in Python
//     (runtime/native_frontend.py), byte-exact response templates built
//     with the same pb2 code as the Python gRPC server;
//   - everything else (OIDC identities, metadata fetches, templated
//     denyWith, wildcard host corpora, …) routes to the Python pipeline
//     over the slow queue — full semantics, lower throughput;
//   - the packed verdict column 0 is exactly the pipeline's decision for a
//     fast-lane config: ∧ over evaluators of (¬cond ∨ rule)
//     (ops/pattern_eval.py eval_verdicts; ref pkg/service/auth_pipeline.go:287-322).
//
// Compiled as part of the _atpuenc single translation unit (pymod.cpp).

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <sys/timerfd.h>
#include <unistd.h>

#include <algorithm>
#include <array>
#include <atomic>
#include <cctype>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

// ---------------------------------------------------------------------------
// HTTP/2 (RFC 9113), the server side of gRPC unary calls, with a complete
// HPACK decoder (RFC 7541).  Here: the wire's constants, the Huffman code and
// the decoder; the connection's state machine, which hands each request to
// the fast lane, is fe::'s ("The framer").  What it implements and what it
// refuses: docs/architecture.md "The HTTP/2 framer".
// ---------------------------------------------------------------------------
namespace h2 {

enum FrameType : uint8_t {
  DATA = 0, HEADERS = 1, PRIORITY = 2, RST_STREAM = 3, SETTINGS = 4,
  PUSH_PROMISE = 5, PING = 6, GOAWAY = 7, WINDOW_UPDATE = 8, CONTINUATION = 9,
};
enum : uint8_t {
  END_STREAM = 0x1, ACK = 0x1, END_HEADERS = 0x4, PADDED = 0x8, PRIORITY_FLAG = 0x20,
};
enum ErrorCode : uint32_t {
  PROTOCOL_ERROR = 1, FLOW_CONTROL_ERROR = 3, STREAM_CLOSED = 5,
  FRAME_SIZE_ERROR = 6, REFUSED_STREAM = 7, COMPRESSION_ERROR = 9,
  ENHANCE_YOUR_CALM = 11,
};
enum : uint16_t {
  S_HEADER_TABLE_SIZE = 1, S_ENABLE_PUSH = 2, S_MAX_CONCURRENT_STREAMS = 3,
  S_INITIAL_WINDOW_SIZE = 4, S_MAX_FRAME_SIZE = 5,
};

static const char PREFACE[] = "PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n";
static const size_t PREFACE_LEN = sizeof(PREFACE) - 1;
static const size_t FRAME_HEAD = 9;
// what this server announces (the stream limit: ref main.go:68-69) ...
static const uint32_t MAX_STREAMS = 10000;
static const int64_t STREAM_WINDOW = 1 << 20;
static const int64_t CONN_WINDOW = 1 << 30;
// ... and leaves at the protocol's defaults: it takes frames of up to 16 KB
// and keeps a decoder table of up to 4 KB
static const uint32_t MAX_FRAME = 16384;
static const uint32_t HPACK_TABLE = 4096;
static const int64_t DEFAULT_WINDOW = 65535;
static const int64_t MAX_WINDOW = 0x7fffffff;
// a header block continued over CONTINUATION frames past this is refused
static const size_t MAX_HEADER_BLOCK = 256 << 10;
// a message past this is not gathered: its stream is answered
// RESOURCE_EXHAUSTED when it ends
static const size_t MAX_MESSAGE = 16 << 20;
// a connection whose unsent answers pass this is not read until its peer
// takes them; past MAX_ACKS control replies (PING and SETTINGS ACKs,
// RST_STREAM) queued while the peer takes nothing, it is closed
static const size_t OUT_CAP = 1 << 20;
static const uint32_t MAX_ACKS = 1000;

static inline uint32_t be24(const uint8_t* p) {
  return ((uint32_t)p[0] << 16) | ((uint32_t)p[1] << 8) | p[2];
}
static inline uint32_t be32(const uint8_t* p) {
  return ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16) | ((uint32_t)p[2] << 8) | p[3];
}
static inline void put24(uint8_t* p, uint32_t v) {
  p[0] = (uint8_t)(v >> 16); p[1] = (uint8_t)(v >> 8); p[2] = (uint8_t)v;
}
static inline void put32(uint8_t* p, uint32_t v) {
  p[0] = (uint8_t)(v >> 24); p[1] = (uint8_t)(v >> 16);
  p[2] = (uint8_t)(v >> 8); p[3] = (uint8_t)v;
}

// ---- HPACK: the static table (Appendix A) ---------------------------------
struct StaticEnt { const char* name; uint8_t nl; const char* value; uint8_t vl; };
#define H2_ENT(n, v) {n, sizeof(n) - 1, v, sizeof(v) - 1}
static const StaticEnt STATIC_TABLE[61] = {
    H2_ENT(":authority", ""), H2_ENT(":method", "GET"), H2_ENT(":method", "POST"),
    H2_ENT(":path", "/"), H2_ENT(":path", "/index.html"), H2_ENT(":scheme", "http"),
    H2_ENT(":scheme", "https"), H2_ENT(":status", "200"), H2_ENT(":status", "204"),
    H2_ENT(":status", "206"), H2_ENT(":status", "304"), H2_ENT(":status", "400"),
    H2_ENT(":status", "404"), H2_ENT(":status", "500"), H2_ENT("accept-charset", ""),
    H2_ENT("accept-encoding", "gzip, deflate"), H2_ENT("accept-language", ""),
    H2_ENT("accept-ranges", ""), H2_ENT("accept", ""),
    H2_ENT("access-control-allow-origin", ""), H2_ENT("age", ""), H2_ENT("allow", ""),
    H2_ENT("authorization", ""), H2_ENT("cache-control", ""),
    H2_ENT("content-disposition", ""), H2_ENT("content-encoding", ""),
    H2_ENT("content-language", ""), H2_ENT("content-length", ""),
    H2_ENT("content-location", ""), H2_ENT("content-range", ""),
    H2_ENT("content-type", ""), H2_ENT("cookie", ""), H2_ENT("date", ""),
    H2_ENT("etag", ""), H2_ENT("expect", ""), H2_ENT("expires", ""), H2_ENT("from", ""),
    H2_ENT("host", ""), H2_ENT("if-match", ""), H2_ENT("if-modified-since", ""),
    H2_ENT("if-none-match", ""), H2_ENT("if-range", ""),
    H2_ENT("if-unmodified-since", ""), H2_ENT("last-modified", ""), H2_ENT("link", ""),
    H2_ENT("location", ""), H2_ENT("max-forwards", ""), H2_ENT("proxy-authenticate", ""),
    H2_ENT("proxy-authorization", ""), H2_ENT("range", ""), H2_ENT("referer", ""),
    H2_ENT("refresh", ""), H2_ENT("retry-after", ""), H2_ENT("server", ""),
    H2_ENT("set-cookie", ""), H2_ENT("strict-transport-security", ""),
    H2_ENT("transfer-encoding", ""), H2_ENT("user-agent", ""), H2_ENT("vary", ""),
    H2_ENT("via", ""), H2_ENT("www-authenticate", ""),
};
#undef H2_ENT

// ---- HPACK: the Huffman code (Appendix B) ---------------------------------
// The code is canonical: the bit length of each of the 257 symbols (EOS
// last) determines every code, so the table is the lengths alone.
static const uint8_t HUFF_LEN[257] = {
    13, 23, 28, 28, 28, 28, 28, 28, 28, 24, 30, 28, 28, 30, 28, 28,
    28, 28, 28, 28, 28, 28, 30, 28, 28, 28, 28, 28, 28, 28, 28, 28,
    6, 10, 10, 12, 13, 6, 8, 11, 10, 10, 8, 11, 8, 6, 6, 6,
    5, 5, 5, 6, 6, 6, 6, 6, 6, 6, 7, 8, 15, 6, 12, 10,
    13, 6, 7, 7, 7, 7, 7, 7, 7, 7, 7, 7, 7, 7, 7, 7,
    7, 7, 7, 7, 7, 7, 7, 7, 8, 7, 8, 13, 19, 13, 14, 6,
    15, 5, 6, 5, 6, 5, 6, 6, 6, 5, 7, 7, 6, 6, 6, 5,
    6, 7, 6, 5, 5, 6, 7, 7, 7, 7, 7, 15, 11, 14, 13, 28,
    20, 22, 20, 20, 22, 22, 22, 23, 22, 23, 23, 23, 23, 23, 24, 23,
    24, 24, 22, 23, 24, 23, 23, 23, 23, 21, 22, 23, 22, 23, 23, 24,
    22, 21, 20, 22, 22, 23, 23, 21, 23, 22, 22, 24, 21, 22, 23, 23,
    21, 21, 22, 21, 23, 22, 23, 23, 20, 22, 22, 22, 23, 22, 22, 23,
    26, 26, 20, 19, 22, 23, 22, 25, 26, 26, 26, 27, 27, 26, 24, 25,
    19, 21, 26, 27, 27, 26, 27, 24, 21, 21, 26, 26, 28, 27, 27, 27,
    20, 24, 20, 21, 22, 21, 21, 23, 22, 22, 25, 25, 24, 24, 26, 23,
    26, 27, 26, 26, 27, 27, 27, 27, 27, 28, 27, 27, 27, 27, 27, 26,
    30,
};
static const int HUFF_EOS = 256;

// canonical decoding: the codes of length L are the L-bit values
// [first[L], limit[L]), and the i-th of them is syms[offset[L] + i]
struct Huffman {
  uint32_t first[31] = {}, limit[31] = {};
  uint16_t offset[31] = {}, syms[257] = {};
  Huffman() {
    uint16_t count[31] = {};
    for (int s = 0; s < 257; ++s) count[HUFF_LEN[s]]++;
    uint32_t code = 0;
    uint16_t at = 0;
    for (int L = 1; L <= 30; ++L) {
      first[L] = code;
      limit[L] = code + count[L];
      offset[L] = at;
      at = (uint16_t)(at + count[L]);
      code = (code + count[L]) << 1;
    }
    uint16_t next[31];
    memcpy(next, offset, sizeof next);
    for (int s = 0; s < 257; ++s) syms[next[HUFF_LEN[s]]++] = (uint16_t)s;
  }
  // appends the decoded string to `out`; false on what 5.2 calls a decoding
  // error: the EOS symbol, or padding longer than 7 bits or not all ones
  bool decode(const uint8_t* p, size_t n, std::string& out) const {
    uint64_t acc = 0;
    int bits = 0;
    size_t i = 0;
    for (;;) {
      while (bits <= 56 && i < n) {
        acc = (acc << 8) | p[i++];
        bits += 8;
      }
      int L = 5;
      uint32_t code = 0;
      for (; L <= 30 && L <= bits; ++L) {
        code = (uint32_t)(acc >> (bits - L)) & ((1u << L) - 1);
        if (code < limit[L]) break;
      }
      if (L > 30 || L > bits)  // no whole code is left: what is, is padding
        return bits <= 7 && (acc & ((1ull << bits) - 1)) == (1ull << bits) - 1;
      const uint16_t sym = syms[offset[L] + (code - first[L])];
      if (sym == HUFF_EOS) return false;
      out.push_back((char)sym);
      bits -= L;
    }
  }
};
static const Huffman HUFF;

// an integer of an N-bit prefix (5.1); false past the input or 2^31
static bool hp_int(const uint8_t*& p, const uint8_t* end, int prefix, uint32_t& out) {
  if (p >= end) return false;
  const uint32_t mask = (1u << prefix) - 1;
  uint64_t v = *p++ & mask;
  if (v < mask) {
    out = (uint32_t)v;
    return true;
  }
  for (int m = 0; m <= 28; m += 7) {
    if (p >= end) return false;
    const uint8_t b = *p++;
    v += (uint64_t)(b & 0x7f) << m;
    if (!(b & 0x80)) {
      if (v > (uint64_t)MAX_WINDOW) return false;
      out = (uint32_t)v;
      return true;
    }
  }
  return false;
}

// a string literal (5.2): raw bytes are handed out where they lie, Huffman-
// coded ones decoded into `scratch`
static bool hp_str(const uint8_t*& p, const uint8_t* end, std::string& scratch,
                   const char*& s, size_t& n) {
  if (p >= end) return false;
  const bool huff = (*p & 0x80) != 0;
  uint32_t len;
  if (!hp_int(p, end, 7, len) || (size_t)(end - p) < len) return false;
  if (huff) {
    scratch.clear();
    if (!HUFF.decode(p, len, scratch)) return false;
    s = scratch.data();
    n = scratch.size();
  } else {
    s = (const char*)p;
    n = len;
  }
  p += len;
  return true;
}

// one connection's decoder: the dynamic table (2.3.2, newest first) and
// the scratch the Huffman-coded strings of one field are decoded into
struct Hpack {
  std::deque<std::pair<std::string, std::string>> dyn;
  size_t size = 0;            // 4.1: the entries' bytes, 32 more each
  size_t max = HPACK_TABLE;   // the table's size as the last update set it
  std::string sname, svalue;

  void evict_to(size_t cap) {
    while (size > cap && !dyn.empty()) {
      size -= dyn.back().first.size() + dyn.back().second.size() + 32;
      dyn.pop_back();
    }
  }
  // 4.4: the entry is copied first, since its name may be an entry the
  // insert evicts; one larger than the table empties it
  void insert(const char* n, size_t nl, const char* v, size_t vl) {
    const size_t es = nl + vl + 32;
    if (es > max) {
      evict_to(0);
      return;
    }
    std::pair<std::string, std::string> e(std::string(n, nl), std::string(v, vl));
    evict_to(max - es);
    dyn.push_front(std::move(e));
    size += es;
  }
  bool entry(uint32_t idx, const char*& n, size_t& nl, const char*& v, size_t& vl) const {
    if (idx == 0) return false;
    if (idx <= 61) {
      const StaticEnt& e = STATIC_TABLE[idx - 1];
      n = e.name; nl = e.nl; v = e.value; vl = e.vl;
      return true;
    }
    if (idx - 62 >= dyn.size()) return false;
    const auto& e = dyn[idx - 62];
    n = e.first.data(); nl = e.first.size(); v = e.second.data(); vl = e.second.size();
    return true;
  }
  // decodes one whole header block, calling field(name, nl, value, vl) for
  // each header field in order; false on a decoding error (a connection
  // error COMPRESSION_ERROR).  A field is handed on before its insert into
  // the table, which may evict what the name points at.
  template <class F>
  bool decode(const uint8_t* p, size_t n, F&& field) {
    const uint8_t* end = p + n;
    bool leading = true;  // 4.2: size updates come first in a block
    while (p < end) {
      const uint8_t b = *p;
      const char *nm, *v;
      size_t nl, vl;
      if (b & 0x80) {  // 6.1 indexed
        uint32_t idx;
        if (!hp_int(p, end, 7, idx) || !entry(idx, nm, nl, v, vl)) return false;
        field(nm, nl, v, vl);
      } else if ((b & 0xe0) == 0x20) {  // 6.3 dynamic table size update
        uint32_t sz;
        if (!leading || !hp_int(p, end, 5, sz) || sz > HPACK_TABLE) return false;
        max = sz;
        evict_to(max);
        continue;
      } else {  // 6.2 literal: with incremental indexing, without, never
        const bool index = (b & 0xc0) == 0x40;
        uint32_t ni;
        if (!hp_int(p, end, index ? 6 : 4, ni)) return false;
        if (ni) {
          const char* unused;
          size_t unused_n;
          if (!entry(ni, nm, nl, unused, unused_n)) return false;
        } else if (!hp_str(p, end, sname, nm, nl)) {
          return false;
        }
        if (!hp_str(p, end, svalue, v, vl)) return false;
        field(nm, nl, v, vl);
        if (index) insert(nm, nl, v, vl);
      }
      leading = false;
    }
    return true;
  }
};

}  // namespace h2

namespace fe {

// ---------------------------------------------------------------------------
// Minimal protobuf walker for envoy CheckRequest
// (field numbers: protos/src/envoy/service/auth/v3/*.proto)
// ---------------------------------------------------------------------------
struct PbView {
  const char* p = nullptr;
  size_t n = 0;
  bool set = false;
  std::string str() const { return std::string(p ? p : "", n); }
};

struct ReqView {
  bool has_attributes = false, has_request = false, has_http = false;
  PbView method, path, host, scheme, query, fragment, protocol;
  PbView source_cert;  // AttributeContext.source.certificate (Peer field 5)
  int64_t size = 0;
  std::vector<std::pair<PbView, PbView>> headers;   // last-wins on dup keys
  std::vector<std::pair<PbView, PbView>> ctx_ext;
};

static bool pb_varint(const char*& p, const char* end, uint64_t& v) {
  v = 0;
  int shift = 0;
  while (p < end && shift < 64) {
    uint8_t b = (uint8_t)*p++;
    v |= (uint64_t)(b & 0x7f) << shift;
    if (!(b & 0x80)) return true;
    shift += 7;
  }
  return false;
}

// returns false on malformed input
static bool pb_skip(const char*& p, const char* end, int wt) {
  uint64_t v;
  switch (wt) {
    case 0: return pb_varint(p, end, v);
    case 1: if (end - p < 8) return false; p += 8; return true;
    case 2:
      if (!pb_varint(p, end, v) || (uint64_t)(end - p) < v) return false;
      p += v; return true;
    case 5: if (end - p < 4) return false; p += 4; return true;
    default: return false;
  }
}

static bool pb_len(const char*& p, const char* end, PbView& out) {
  uint64_t v;
  if (!pb_varint(p, end, v) || (uint64_t)(end - p) < v) return false;
  out.p = p;
  out.n = (size_t)v;
  out.set = true;
  p += v;
  return true;
}

static bool parse_map_entry(PbView msg, PbView& k, PbView& v) {
  const char* p = msg.p;
  const char* end = msg.p + msg.n;
  while (p < end) {
    uint64_t tag;
    if (!pb_varint(p, end, tag)) return false;
    int f = (int)(tag >> 3), wt = (int)(tag & 7);
    if (f == 1 && wt == 2) { if (!pb_len(p, end, k)) return false; }
    else if (f == 2 && wt == 2) { if (!pb_len(p, end, v)) return false; }
    else if (!pb_skip(p, end, wt)) return false;
  }
  return true;
}

static bool parse_http(PbView msg, ReqView& rv) {
  const char* p = msg.p;
  const char* end = msg.p + msg.n;
  while (p < end) {
    uint64_t tag;
    if (!pb_varint(p, end, tag)) return false;
    int f = (int)(tag >> 3), wt = (int)(tag & 7);
    PbView v;
    switch (f) {
      case 2: if (wt != 2 || !pb_len(p, end, v)) return false; rv.method = v; break;
      case 3: {  // headers map entry
        if (wt != 2 || !pb_len(p, end, v)) return false;
        PbView k, val;
        if (!parse_map_entry(v, k, val)) return false;
        rv.headers.emplace_back(k, val);
        break;
      }
      case 4: if (wt != 2 || !pb_len(p, end, v)) return false; rv.path = v; break;
      case 5: if (wt != 2 || !pb_len(p, end, v)) return false; rv.host = v; break;
      case 6: if (wt != 2 || !pb_len(p, end, v)) return false; rv.scheme = v; break;
      case 7: if (wt != 2 || !pb_len(p, end, v)) return false; rv.query = v; break;
      case 8: if (wt != 2 || !pb_len(p, end, v)) return false; rv.fragment = v; break;
      case 9: {
        uint64_t u;
        if (wt != 0 || !pb_varint(p, end, u)) return false;
        rv.size = (int64_t)u;
        break;
      }
      case 10: if (wt != 2 || !pb_len(p, end, v)) return false; rv.protocol = v; break;
      default: if (!pb_skip(p, end, wt)) return false;
    }
  }
  return true;
}

static bool parse_check_request(const char* data, size_t n, ReqView& rv) {
  const char* p = data;
  const char* end = data + n;
  PbView attrs;
  while (p < end) {
    uint64_t tag;
    if (!pb_varint(p, end, tag)) return false;
    int f = (int)(tag >> 3), wt = (int)(tag & 7);
    if (f == 1 && wt == 2) {
      if (!pb_len(p, end, attrs)) return false;
      rv.has_attributes = true;
    } else if (!pb_skip(p, end, wt)) return false;
  }
  if (!attrs.set) return true;
  p = attrs.p;
  end = attrs.p + attrs.n;
  while (p < end) {
    uint64_t tag;
    if (!pb_varint(p, end, tag)) return false;
    int f = (int)(tag >> 3), wt = (int)(tag & 7);
    if (f == 1 && wt == 2) {  // source peer (certificate at field 5)
      PbView peer;
      if (!pb_len(p, end, peer)) return false;
      const char* q = peer.p;
      const char* qe = peer.p + peer.n;
      while (q < qe) {
        uint64_t t2;
        if (!pb_varint(q, qe, t2)) return false;
        int f2 = (int)(t2 >> 3), w2 = (int)(t2 & 7);
        if (f2 == 5 && w2 == 2) {
          if (!pb_len(q, qe, rv.source_cert)) return false;
        } else if (!pb_skip(q, qe, w2)) return false;
      }
    } else if (f == 4 && wt == 2) {  // request
      PbView req;
      if (!pb_len(p, end, req)) return false;
      rv.has_request = true;
      const char* q = req.p;
      const char* qe = req.p + req.n;
      while (q < qe) {
        uint64_t t2;
        if (!pb_varint(q, qe, t2)) return false;
        int f2 = (int)(t2 >> 3), w2 = (int)(t2 & 7);
        if (f2 == 2 && w2 == 2) {  // http
          PbView http;
          if (!pb_len(q, qe, http)) return false;
          rv.has_http = true;
          if (!parse_http(http, rv)) return false;
        } else if (!pb_skip(q, qe, w2)) return false;
      }
    } else if (f == 10 && wt == 2) {  // context_extensions entry
      PbView v, k, val;
      if (!pb_len(p, end, v)) return false;
      if (!parse_map_entry(v, k, val)) return false;
      rv.ctx_ext.emplace_back(k, val);
    } else if (!pb_skip(p, end, wt)) return false;
  }
  return true;
}

// last-wins lookup (protobuf map semantics on duplicate keys)
static const PbView* map_get(const std::vector<std::pair<PbView, PbView>>& m,
                             const char* key, size_t klen) {
  const PbView* out = nullptr;
  for (const auto& kv : m)
    if (kv.first.n == klen && memcmp(kv.first.p, key, klen) == 0) out = &kv.second;
  return out;
}

// ---------------------------------------------------------------------------
// Snapshot: everything the fast lane needs, swapped atomically on reconcile
// ---------------------------------------------------------------------------
enum PlanKind {
  K_CONST = 0, K_METHOD, K_PATH, K_URL_PATH, K_QUERY, K_HOST, K_SCHEME,
  K_PROTOCOL, K_SIZE, K_FRAGMENT, K_HEADER, K_CTX_EXT,
};

struct FastPlan {
  int32_t attr;
  int kind;
  std::string key;              // K_HEADER / K_CTX_EXT
  // K_CONST precomputed encoding:
  int32_t const_vid = 0;
  bool const_missing = false;   // missing/null → no member write
  std::vector<int32_t> const_members;
  std::string const_bytes;      // byte-slot payload (raw value bytes)
  bool const_byte_ovf = false;
};

struct VarEnt {
  int32_t idx;                  // var_plans index
  int64_t exp_ns;               // CLOCK_REALTIME expiry; INT64_MAX = static
  int32_t ok_idx = -1;          // var_oks index (per-identity OK response
                                // bytes — response-template configs); -1 =
                                // the config's default OK
  int32_t deny_idx = -1;        // var_denies index (per-identity DENY bytes
                                // — denyWith templates over the identity)
};

// one identity source of a config (multi-identity configs carry several,
// in pipeline priority-then-declaration order — identity is an OR,
// ref pkg/service/auth_pipeline.go:203-258)
struct CredSource {
  int cred_kind = 0;            // 1 auth header, 2 custom header, 3 cookie,
                                // 4 query, 5 client certificate
  std::string cred_key;
  // static (API key): the full key set is known at refresh time — each
  // key's auth.identity.* operands resolved to constant plan variants
  std::unordered_map<std::string, VarEnt> variants;
  std::deque<std::vector<FastPlan>> var_plans;       // deque: stable refs
  std::deque<std::string> var_oks;                   // per-key OK bytes
  std::deque<std::string> var_denies;                // per-key DENY bytes
  // dyn (OIDC/JWT, mTLS): the variant map is a verified-credential cache
  // registered at runtime by the slow lane.  Entries hold their plans by
  // shared_ptr so overwrites and expiry sweeps reclaim memory immediately
  // while a mid-request reader keeps its copy alive without the lock.
  bool dyn = false;
  struct DynVar {
    std::shared_ptr<const std::vector<FastPlan>> plans;
    int64_t exp_ns;
    // per-credential OK / DENY response bytes (response / denyWith
    // templates over the identity); null = the config's defaults
    std::shared_ptr<const std::string> ok;
    std::shared_ptr<const std::string> deny;
  };
  std::unordered_map<std::string, DynVar> dyn_variants;
};

struct FastConfig {
  int32_t row = 0;
  int32_t shard = 0;            // owning mp shard (sharded corpora; else 0)
  // bytes of a value the device scans for this config: its size class's
  // width (compiler/compile.py class_device_width), at most the snapshot's
  // DVB (the slot arrays' stride, the corpus's widest class's); a longer
  // value overflows to scan_overflow
  int32_t dvb = 0;
  bool has_batch = true;        // false → identity-only: decide entirely here
  // hybrid lane: the kernel covers only part of the authorization phase
  // (procedural Rego / SAR / SpiceDB evaluators stay in Python).  A kernel
  // DENY answers immediately — ∧-semantics, any authz failure denies with
  // the same config bytes — while a kernel PASS hands the RAW request to
  // the slow lane for the full pipeline (which re-runs the covered
  // patterns too: correct by construction, and they are kernel-batched
  // there as well)
  bool hybrid = false;
  std::vector<FastPlan> plans;
  bool needs_split = false;     // any K_URL_PATH / K_QUERY plan
  std::string ok_msg, deny_msg; // CheckResponse payloads (pb2-built in Python)
  // identity sources (empty = anonymous).  A request authenticates via the
  // first source whose credential resolves a variant; an extractable dyn
  // credential that misses its cache routes to the slow lane (it may still
  // verify); with no authentication at all the response is the
  // all-sources-failed template for the observed extraction bitmask
  std::vector<CredSource> sources;
  // [2^n_static] UNAUTHENTICATED templates indexed by which STATIC
  // sources' credentials were present (present ⇒ invalid; absent ⇒
  // missing; dyn sources reaching this path are always missing)
  std::vector<std::string> unauth_msgs;
  std::string ns, name;         // per-authconfig metric labels
};

// per-fc cap on runtime-registered variants (attacker-supplied token floods
// must not grow the map unboundedly; beyond the cap new tokens keep being
// served — correctly — by the slow lane)
static const size_t DYN_VARIANT_CAP = 65536;

static inline int64_t now_realtime_ns() {
  struct timespec ts;
  clock_gettime(CLOCK_REALTIME, &ts);
  return (int64_t)ts.tv_sec * 1000000000LL + ts.tv_nsec;
}

static inline int64_t now_mono_ns() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (int64_t)ts.tv_sec * 1000000000LL + ts.tv_nsec;
}

// auth_server_authconfig_duration_seconds bucket bounds — EXACTLY
// prometheus_client's default Histogram buckets, so drained counts map 1:1
// onto the same series the Python pipeline observes
// (ref pkg/service/auth_pipeline.go:26-36 records per-request duration
// histograms; the fast lane records them here and Python folds them in)
static const int64_t DUR_BOUNDS_NS[] = {
    5000000LL,    10000000LL,   25000000LL,  50000000LL,  75000000LL,
    100000000LL,  250000000LL,  500000000LL, 750000000LL, 1000000000LL,
    2500000000LL, 5000000000LL, 7500000000LL, 10000000000LL};
static const int N_DUR_BUCKETS = 15;  // 14 bounds + +Inf
// per-fc slot layout in fc_durs: [15 buckets][sum_ns] = 16 u64
static const int DUR_STRIDE = N_DUR_BUCKETS + 1;

// on-box stage bounds (µs-scale: the stages a co-located chip pays —
// queue-wait enq→flush, execute flush→complete, respond complete→submit)
static const int64_t STAGE_BOUNDS_NS[] = {
    10000LL,     25000LL,     50000LL,     100000LL,   250000LL,
    500000LL,    1000000LL,   2500000LL,   5000000LL,  10000000LL,
    25000000LL,  50000000LL,  100000000LL, 250000000LL, 1000000000LL};
static const int N_STAGE_BUCKETS = 16;  // 15 bounds + +Inf

static inline int dur_bucket(int64_t ns) {
  for (int i = 0; i < N_DUR_BUCKETS - 1; ++i)
    if (ns <= DUR_BOUNDS_NS[i]) return i;
  return N_DUR_BUCKETS - 1;
}

static inline int stage_bucket(int64_t ns) {
  for (int i = 0; i < N_STAGE_BUCKETS - 1; ++i)
    if (ns <= STAGE_BOUNDS_NS[i]) return i;
  return N_STAGE_BUCKETS - 1;
}

// ---------------------------------------------------------------------------
// The loop clock: where the one epoll thread's time goes, by phase
// (docs/observability.md "The front end's loop clock").  The shape of the
// batch stage clock (runtime/batch_stages.py): rows {count, sum_ns, max_ns},
// cumulative, read as the difference of two scrapes, on CLOCK_MONOTONIC
// (Python's time.monotonic_ns()), always on.  A stamp charges what lies
// since the last stamp to the phase it names, so the thread's phases add up
// to its wall time and a phase holds its self time: `read` is a connection
// event's recv + frame walk less the `parse`, `encode`, `ovf_scan` and `cut`
// its requests stamped on the way.  The names are written here and nowhere
// else: fe_loop_clock() carries them out, the thread's phases under
// `phases` and the rows that are not phases of it under `rows`.
// ---------------------------------------------------------------------------
enum ClockRowId {
  PH_IDLE = 0,   // inside epoll_wait; count: wakes
  PH_READ,       // recv + the framer's walk, HPACK, stream bookkeeping; count: recv calls
  PH_PARSE,      // process_check entry -> the chosen FastConfig; count: Check requests
  PH_ENCODE,     // ensure_fill + zero_row + encode_fast; count: rows encoded
  PH_OVF_SCAN,   // scan_overflow of a value past its config's width; count: such rows
  PH_CUT,        // flush_batch; count: cuts flushed
  PH_RESPOND,    // drain_done's submit loop; count: answers submitted
  PH_WRITE,      // conn_pump (mem_send + send); count: send calls
  PH_OTHER,      // accept, close, direct answers, push_slow, eventfd/timerfd reads; count: events
  N_LOOP_PHASES,
  // not phases of the thread: a wake's busy stretch, and the three
  // per-request stages of the histograms with their exact sums
  ROW_TURN = N_LOOP_PHASES,
  ROW_REQ_WAIT,
  ROW_REQ_EXEC,
  ROW_REQ_RESPOND,
  // counts alone, of the overflow scan (scan_overflow): the DFAs it entered
  // and the table loads it made.  loads / dfas is the bytes a DFA read
  // before its verdict was settled: the value's length where none absorbs
  ROW_OVF_DFAS,
  ROW_OVF_LOADS,
  // counts alone, a Check request: the bytes of its CheckRequest message
  // and the headers parse_check_request found in it (over `parse`'s count)
  ROW_REQ_BYTES,
  ROW_REQ_HEADERS,
  // the two syscalls, each inside its phase: the time and calls in `recv`
  // (a part of `read`) and in `send` (a part of `write`); what their phase
  // spends besides is the framer's
  ROW_RECV,
  ROW_SEND,
  // a count alone: the Check requests parsed where recv put them (the
  // message whole in one DATA frame, not gathered), over `parse`'s count
  ROW_MSG_INPLACE,
  // a count alone: the cuts the batch window's timer made, the slot not
  // full, over `cut`'s count (every cut)
  ROW_CUT_TIMER,
  N_CLOCK_ROWS
};
static const char* const CLOCK_ROW_NAMES[N_CLOCK_ROWS] = {
    "idle", "read", "parse", "encode", "ovf_scan", "cut", "respond", "write",
    "other", "turn", "req_wait", "req_exec", "req_respond", "ovf_dfas",
    "ovf_loads", "req_bytes", "req_headers", "recv", "send", "msg_inplace",
    "cut_timer"};

// two branches off the thread's path that say who holds it back, each with
// its operator's use in docs/observability.md: the peer does not read
// (`write` is then the peer's time), the pipeline behind the cut is full
// (`fill` then passes the window)
enum LoopCounterId { LC_SEND_BLOCKED = 0, LC_CUTS_DEFERRED, N_LOOP_COUNTERS };
static const char* const LOOP_COUNTER_NAMES[N_LOOP_COUNTERS] = {
    "send_blocked", "cuts_deferred"};

// a turn is slow when its busy stretch passed SLOW_TURN_BUSY_NS, or when the
// idle before it passed SLOW_TURN_IDLE_NS with a row in the filling slot or a
// cut not yet completed (an idle server's 100 ms time-outs do not qualify)
static const int64_t SLOW_TURN_BUSY_NS = 5000000LL;
static const int64_t SLOW_TURN_IDLE_NS = 50000000LL;
static const int N_SLOW_TURNS = 64;

struct SlowTurn {
  int64_t wake_mono_ns, idle_ns, busy_ns;
  int64_t events, requests, answers;
};

struct ClockRow {
  std::atomic<uint64_t> count{0}, sum_ns{0}, max_ns{0};
  // one writer (the epoll thread): a load and a store, no locked instruction
  void add(int64_t ns) {
    const uint64_t v = ns > 0 ? (uint64_t)ns : 0;
    sum_ns.store(sum_ns.load(std::memory_order_relaxed) + v, std::memory_order_relaxed);
    if (v > max_ns.load(std::memory_order_relaxed)) max_ns.store(v, std::memory_order_relaxed);
  }
  void bump(uint64_t n = 1) {
    count.store(count.load(std::memory_order_relaxed) + n, std::memory_order_relaxed);
  }
  // any thread (complete_batch runs on Python's): once a cut
  void add_shared(uint64_t n, uint64_t total_ns, uint64_t longest_ns) {
    count.fetch_add(n, std::memory_order_relaxed);
    sum_ns.fetch_add(total_ns, std::memory_order_relaxed);
    uint64_t seen = max_ns.load(std::memory_order_relaxed);
    while (longest_ns > seen &&
           !max_ns.compare_exchange_weak(seen, longest_ns, std::memory_order_relaxed)) {}
  }
};

struct LoopClock {
  ClockRow rows[N_CLOCK_ROWS];
  std::atomic<uint64_t> counters[N_LOOP_COUNTERS] = {};
  // the last stamp (written by the epoll thread alone).  For a reader: the
  // table is whole up to here, and what the thread has spent since is
  // charged at its next stamp
  std::atomic<int64_t> mark{0};
  // the ring of slow turns: written for such turns alone, so its lock is
  // off the thread's path
  std::mutex turns_mu;
  SlowTurn turns[N_SLOW_TURNS];
  uint64_t n_turns = 0;

  // charge what lies since the last stamp to `row`; returns now
  int64_t stamp(int row) {
    const int64_t now = now_mono_ns();
    rows[row].add(now - mark.load(std::memory_order_relaxed));
    mark.store(now, std::memory_order_relaxed);
    return now;
  }
  void count(int c, uint64_t n = 1) {
    counters[c].store(counters[c].load(std::memory_order_relaxed) + n,
                      std::memory_order_relaxed);
  }
  // a wake's busy stretch ended at the last stamp
  void end_turn(int64_t woke, int64_t idle_ns, bool owed, int events,
                uint64_t requests, uint64_t answers) {
    const int64_t busy = mark.load(std::memory_order_relaxed) - woke;
    rows[ROW_TURN].add(busy);
    rows[ROW_TURN].bump();
    if (busy > SLOW_TURN_BUSY_NS || (owed && idle_ns > SLOW_TURN_IDLE_NS)) {
      std::lock_guard<std::mutex> lk(turns_mu);
      turns[n_turns++ % N_SLOW_TURNS] = {woke, idle_ns, busy, events,
                                         (int64_t)requests, (int64_t)answers};
    }
  }
};

// one DFA leaf of a config: the attr it reads, its dfa table row, its column
// in the config's own cpu_dense payload
struct DfaRef { int32_t attr; int32_t row; int32_t col; };

// what the snapshot's [R, St] state flags say (compiler/compile.py
// dfa_state_flags): the state accepts; every one of its 256 transitions
// returns to it, so no further byte can change the DFA's verdict
static const uint8_t DFA_ACCEPTS = 1, DFA_ABSORBS = 2;

// one DFA of the overflow scan's live set
struct ScanLane {
  const uint8_t* trans;  // its [St, 256] table, of Snapshot::dfa_state_bytes
  const uint8_t* flags;  // its [St] state flags
  int32_t col;           // its column of the row's cpu_dense
  uint32_t state;
};

struct Entry {
  uint32_t conn_id;
  int32_t stream_id;
  int32_t fc;
  int64_t t_enq;  // CLOCK_MONOTONIC at encode time (stage/duration hists)
  // per-identity OK / DENY response overrides (response + denyWith
  // templates); the _hold fields keep dyn bytes alive until completion
  const std::string* ok_msg = nullptr;
  std::shared_ptr<const std::string> ok_hold;
  const std::string* deny_msg = nullptr;
  std::shared_ptr<const std::string> deny_hold;
  // hybrid configs only: the raw CheckRequest pb, kept so a kernel PASS
  // can hand the request to the slow lane at completion time (the stream
  // buffer is not safely reachable from a dispatch thread)
  std::string raw;
};

struct Slot {
  // sharded corpora carry a leading shard axis: [Bmax, S, ...]; the
  // single-corpus layout is the S=1 special case of the same strides
  char* attrs_val = nullptr;     // [Bmax, S, A] int16/int32
  char* members = nullptr;       // [Bmax, S, M, K] int16/int32
  uint8_t* cpu_dense = nullptr;  // [Bmax, S, C] bool, C = c_own: the row's own config's columns
  int32_t* config_id = nullptr;  // [Bmax] row within the owning shard
  int32_t* shard_of = nullptr;   // [Bmax] owning shard (null for S=1)
  uint8_t* attr_bytes = nullptr; // [Bmax, S, NB, DVB]
  uint8_t* byte_ovf = nullptr;   // [Bmax, S, NB] bool
  // per row: the longest value written into its attr_bytes (every byte of
  // the row past it, in every slot, is zero: what zero_row clears, what a
  // launch trims to and what the verdict cache keys on), and the value
  // bytes its DFAs read, a DFA a byte: [0] on the device (values inside the
  // config's width), [1] handed to scan_overflow
  uint16_t* byte_used = nullptr; // [Bmax]
  uint32_t* dfa_bytes = nullptr; // [Bmax, 2]
};

struct Snapshot {
  int64_t id = 0;
  const Interner* interner = nullptr;  // borrowed from Policy (Python-owned)
  int A = 0, M = 0, K = 0, C = 0, NB = 0, DVB = 0;
  int S = 1;  // mp shards (sharded corpora stack per-shard metadata)
  bool elem16 = false;
  std::vector<int32_t> attr_member_slot;  // [S*A] → M row or -1
  std::vector<int32_t> attr_byte_slot_v;  // [S*A] → NB row or -1
  int G = 0;  // config rows per shard
  std::vector<std::vector<DfaRef>> cfg_dfas;  // [S*G]; rows globalized
  std::vector<uint16_t> cfg_slot_dfas;  // [S*G, NB]: DFAs of the config that read the byte slot
  // next states: u8, or u16 where St passes 256 (dfa_state_bytes 2)
  std::vector<uint8_t> dfa_trans;  // [S*R, St, 256] x dfa_state_bytes
  std::vector<uint8_t> dfa_flags;  // [S*R, St]: DFA_ACCEPTS | DFA_ABSORBS
  int dfa_S = 0;
  int dfa_state_bytes = 1;
  // the overflow scan's live set (the epoll thread's alone), sized at
  // install to the most DFA leaves any config has: no allocation a request
  std::vector<ScanLane> scan_lanes;
  // head-based trace sampling: route every Nth fast-eligible request to
  // the slow lane for full span export (0 = tracing off → all fast).
  // The reference traces every request (ref pkg/service/auth.go:261); the
  // fast lane never touches Python per request, so sampling trades span
  // completeness for keeping the native throughput while observability is on
  int64_t trace_every = 0;
  // host / "*.suffix" wildcard → fc idx, -1 = slow lane
  std::unordered_map<std::string, int32_t> host_map;
  std::vector<FastConfig> fcs;
  // guards every fc's variants/var_plans once dynamic registration starts
  // (epoll thread looks up; the slow lane inserts via fe_add_variant).
  // FastPlan vectors are immutable after publication, so a looked-up
  // pointer stays valid after unlock (deque push_back never moves elements)
  std::mutex var_mu;
  // batch slots (numpy arrays owned by Python until retirement)
  std::vector<Slot> slots;
  std::vector<int> free_slots;
  std::vector<std::vector<Entry>> slot_entries;
  std::vector<int> slot_count;
  int pending_batches = 0;
  // global response templates (pb2-built in Python for byte parity with the
  // Python gRPC server)
  std::string invalid_msg, notfound_msg, health_msg;
  // per-fc direct-decision counters [ok, unauth_missing, unauth_invalid] —
  // decisions that never enter a batch; the Python dispatcher folds them
  // into the pipeline's Prometheus series (fe_drain_fc_counts)
  std::unique_ptr<std::atomic<uint64_t>[]> fc_counts;
  // per-fc request-duration histograms, DUR_STRIDE u64 each (15 prom
  // buckets + sum_ns) — drained into
  // auth_server_authconfig_duration_seconds (fe_drain_durations)
  std::unique_ptr<std::atomic<uint64_t>[]> fc_durs;
  // monotonic flush time of each slot's current batch, and the arrival of
  // its first row (process_check's entry stamp): the cut's `fill` stage
  std::vector<int64_t> slot_flush_ns;
  std::vector<int64_t> slot_first_ns;
};

// ---------------------------------------------------------------------------
// The framer: connections and their streams (the read and write sides below)
// ---------------------------------------------------------------------------
enum StreamKind { SK_UNSET = 0, SK_CHECK, SK_HEALTH, SK_OTHER };

// one client stream, from its HEADERS until its answer is written
struct StreamSt {
  int32_t sid = 0;             // 0: a free slot of the table
  uint8_t kind = SK_UNSET;
  bool compressed = false;     // grpc-encoding other than identity
  bool half_closed = false;    // END_STREAM received: the request is whole
  bool answered = false;       // an answer is under way, its DATA held
  bool too_big = false;        // its message passed MAX_MESSAGE: nothing more is kept
  int64_t recv_used = 0;       // DATA taken since our last WINDOW_UPDATE for it
  int64_t send_window = 0;     // what the peer lets us send on it
  std::string body;            // a message gathered over DATA frames
  std::string held;            // answer DATA the windows would not take yet
};

// a connection's streams: a flat table, open-addressed by stream id (client
// ids are odd and rise, so id/2 spreads them), half full at most.  No node
// and no allocation a stream once it has grown to the connection's
// concurrency.  A pointer into it lasts until the next insert or erase.
struct StreamTable {
  std::vector<StreamSt> slots = std::vector<StreamSt>(64);
  size_t n = 0;

  size_t home(int32_t sid) const { return ((uint32_t)sid >> 1) & (slots.size() - 1); }
  StreamSt* find(int32_t sid) {
    const size_t mask = slots.size() - 1;
    for (size_t i = home(sid);; i = (i + 1) & mask) {
      if (slots[i].sid == sid) return &slots[i];
      if (slots[i].sid == 0) return nullptr;
    }
  }
  StreamSt* insert(int32_t sid) {
    if (2 * (n + 1) > slots.size()) {
      std::vector<StreamSt> old(slots.size() * 2);
      old.swap(slots);
      for (StreamSt& st : old)
        if (st.sid) *place(st.sid) = std::move(st);
    }
    StreamSt* st = place(sid);
    st->sid = sid;
    n++;
    return st;
  }
  // backward-shift deletion: what follows the hole in its probe run moves
  // up where its home allows, so no tombstone is left
  void erase(StreamSt* st) {
    const size_t mask = slots.size() - 1;
    size_t hole = (size_t)(st - slots.data());
    slots[hole] = StreamSt();
    n--;
    for (size_t j = (hole + 1) & mask; slots[j].sid != 0; j = (j + 1) & mask) {
      if (((j - home(slots[j].sid)) & mask) >= ((j - hole) & mask)) {
        slots[hole] = std::move(slots[j]);
        slots[j] = StreamSt();
        hole = j;
      }
    }
  }

 private:
  StreamSt* place(int32_t sid) {
    const size_t mask = slots.size() - 1;
    size_t i = home(sid);
    while (slots[i].sid != 0) i = (i + 1) & mask;
    return &slots[i];
  }
};

// the read buffer: one recv of 64 KB at least lands past the tail of a
// partial frame (at most a frame head and 16 KB) carried to its front
static const size_t RBUF_BYTES = 65536 + h2::FRAME_HEAD + h2::MAX_FRAME;

struct Conn {
  int fd = -1;
  uint32_t id = 0;
  // read side: the buffer recv fills and the framer walks in place
  std::unique_ptr<uint8_t[]> rbuf{new uint8_t[RBUF_BYTES]};
  size_t rlen = 0;
  bool preface = false;        // the client's preface seen
  bool settings = false;       // its first frame, a SETTINGS, seen
  int32_t last_sid = 0;        // the highest stream the client opened
  h2::Hpack hpack;
  StreamTable streams;
  // a header block continued over CONTINUATION frames
  int32_t cont_sid = 0;
  uint8_t cont_flags = 0;
  std::string cont_block;
  int64_t recv_window = h2::CONN_WINDOW;  // what the peer may still send
  // write side: the peer's settings and its window for our DATA
  int64_t send_window = h2::DEFAULT_WINDOW;
  int64_t peer_window = h2::DEFAULT_WINDOW;  // its SETTINGS_INITIAL_WINDOW_SIZE
  uint32_t peer_max_frame = h2::MAX_FRAME;
  uint32_t enc_table = h2::HPACK_TABLE;  // our encoder's table size
  bool table_update = false;   // our next header block opens with a size update
  std::vector<int32_t> held;   // streams whose answer waits on a window
  std::string outbuf;
  size_t out_off = 0;          // outbuf's first byte not yet sent
  uint32_t acks = 0;           // control replies queued since a send last moved bytes
  uint32_t events = EPOLLIN;   // what epoll watches: EPOLLIN unless past OUT_CAP
};

struct Done {
  uint32_t conn_id;
  int32_t stream_id;
  std::string msg;       // CheckResponse payload (no gRPC prefix)
  int grpc_status = 0;   // non-zero → trailers-only error response
  int64_t t_done = 0;    // completion time (respond-stage histogram)
};

struct SlowReq {
  uint64_t id;
  std::string bytes;     // raw CheckRequest pb
};

struct SlowPending {
  uint32_t conn_id;
  int32_t stream_id;
};

// events to Python
enum EvKind { EV_TIMEOUT = 0, EV_BATCH = 1, EV_SNAP_RETIRED = 3, EV_STOPPED = 4 };
// d: for EV_BATCH, the slot's flush time (CLOCK_MONOTONIC ns, the clock of
// Python's time.monotonic_ns()) — the start of the batch's `pickup` stage;
// e: for EV_BATCH, the cut's rows with at least one value past their
// config's width, whose DFAs the encoder scanned here (the ledger's
// `dfa_ovf_rows`);
// f: for EV_BATCH, the arrival of the cut's first row, on d's clock — the
// start of the batch's `fill` stage
struct Event { int kind; int64_t a, b, c, d, e, f; };

struct Server {
  // config
  int port = 0;
  int bound_port = 0;
  bool any_addr = false;  // bind 0.0.0.0 (servers) vs loopback (bench/tests)
  int bmax = 1024;
  int nslots = 8;
  long window_us = 2000;
  size_t slow_cap = 65536;
  std::string health_msg;  // pre-first-swap health reply

  // epoll machinery
  int epfd = -1, listen_fd = -1, evfd = -1, tfd = -1;
  std::thread thr;
  std::atomic<bool> running{false};

  // shared state
  std::mutex mu;
  std::unordered_map<uint32_t, Conn*> conns;
  uint32_t next_conn_id = 1;
  std::shared_ptr<Snapshot> cur;                      // swapped under mu
  std::unordered_map<int64_t, std::shared_ptr<Snapshot>> snaps;
  // snapshot the epoll thread is mid-request on (under mu): retirement
  // must skip it so direct-decision counter bumps are never lost to an
  // already-drained, erased snapshot
  Snapshot* epoll_pin = nullptr;
  // current filling batch (epoll thread only, but slot recycle under mu)
  int fill_slot = -1;
  int fill_count = 0;
  int fill_ovf_rows = 0;      // rows of the filling batch with an overflowed value
  bool fill_row_ovf = false;  // the row being encoded has one
  std::shared_ptr<Snapshot> fill_snap;
  bool timer_armed = false;

  // queues
  std::mutex done_mu;   // done_q only — its own lock so completion storms
                        // from dispatch/slow threads don't contend with
                        // everything else S->mu guards
  std::deque<Done> done_q;                            // under done_mu; evfd wakes epoll
  std::mutex batch_mu;
  std::condition_variable batch_cv;
  std::deque<Event> batch_events;
  std::mutex slow_mu;
  std::condition_variable slow_cv;
  std::deque<SlowReq> slow_q;
  bool stopping = false;
  std::unordered_map<uint64_t, SlowPending> slow_pending;  // under mu
  uint64_t next_slow_id = 1;

  // stats
  std::atomic<uint64_t> n_fast{0}, n_slow{0}, n_notfound{0}, n_invalid{0},
      n_health{0}, n_allowed{0}, n_denied{0}, n_dfa_ovf{0}, n_slow_shed{0},
      n_hybrid{0},
      n_parse_err{0}, n_conns{0}, n_unauth{0}, n_direct_ok{0}, n_dyn_hit{0},
      n_dyn_miss{0}, n_dyn_add{0}, n_trace_sampled{0};
  std::atomic<uint64_t> trace_ctr{0};
  // on-box stage histograms (server-wide): queue-wait (encode→flush),
  // execute (flush→complete_batch), respond (complete→HTTP/2 submit)
  std::atomic<uint64_t> stage_wait[N_STAGE_BUCKETS] = {};
  std::atomic<uint64_t> stage_exec[N_STAGE_BUCKETS] = {};
  std::atomic<uint64_t> stage_respond[N_STAGE_BUCKETS] = {};
  // what fe_stage_hist() last handed out of the three req_* rows' sum_ns
  // (its callers hold the interpreter lock: one at a time)
  uint64_t hist_drained[3] = {};
  // the loop clock, and the cuts flushed and not yet completed: it moves
  // where a snapshot's pending_batches does, under mu, so it cannot drift
  // from them.  What a long idle is held against
  LoopClock clk;
  std::atomic<int64_t> cuts_owed{0};
  // duration-histogram leftovers of retired snapshots (key ns+'\x1f'+name;
  // under mu)
  std::unordered_map<std::string, std::array<uint64_t, DUR_STRIDE>> dur_leftover;
  // fc counters of retired snapshots not yet drained (key ns+'\x1f'+name;
  // under mu)
  std::unordered_map<std::string, std::array<uint64_t, 3>> fc_leftover;
};

static Server* g_srv = nullptr;

// ---- the framer's write side (epoll thread only) --------------------------
// Every header block this server sends is constant bytes, built once: the
// encoder never uses its dynamic table.  An answer is HEADERS (`:status 200`
// as static index 8, `content-type: application/grpc` as a literal without
// indexing), DATA (the 5-byte gRPC prefix and the message) and trailers
// (`grpc-status: 0`, END_STREAM); a trailers-only error is one HEADERS.

static const uint8_t RESP_BLOCK[] = {
    0x88, 0x0f, 0x10, 0x10, 'a', 'p', 'p', 'l', 'i', 'c', 'a', 't',
    'i', 'o', 'n', '/', 'g', 'r', 'p', 'c'};
static const uint8_t STATUS_NAME[] = {
    0x00, 0x0b, 'g', 'r', 'p', 'c', '-', 's', 't', 'a', 't', 'u', 's'};

// an answer's bytes but the message, stream ids and DATA length zero
struct AnswerBytes {
  // HEADERS frame, DATA frame head, gRPC prefix
  uint8_t head[2 * h2::FRAME_HEAD + sizeof RESP_BLOCK + 5] = {};
  // the trailers' HEADERS frame
  uint8_t tail[h2::FRAME_HEAD + sizeof STATUS_NAME + 2] = {};
  static constexpr size_t DATA_AT = h2::FRAME_HEAD + sizeof RESP_BLOCK;
  AnswerBytes() {
    h2::put24(head, sizeof RESP_BLOCK);
    head[3] = h2::HEADERS;
    head[4] = h2::END_HEADERS;
    memcpy(head + h2::FRAME_HEAD, RESP_BLOCK, sizeof RESP_BLOCK);
    head[DATA_AT + 3] = h2::DATA;
    h2::put24(tail, sizeof STATUS_NAME + 2);
    tail[3] = h2::HEADERS;
    tail[4] = h2::END_HEADERS | h2::END_STREAM;
    memcpy(tail + h2::FRAME_HEAD, STATUS_NAME, sizeof STATUS_NAME);
    tail[h2::FRAME_HEAD + sizeof STATUS_NAME] = 1;
    tail[h2::FRAME_HEAD + sizeof STATUS_NAME + 1] = '0';
  }
};
static const AnswerBytes ANSWER;

static inline uint8_t* out_frame(Conn* c, uint32_t len, uint8_t type, uint8_t flags,
                                 int32_t sid) {
  const size_t at = c->outbuf.size();
  c->outbuf.resize(at + h2::FRAME_HEAD + len);
  uint8_t* f = (uint8_t*)&c->outbuf[at];
  h2::put24(f, len);
  f[3] = type;
  f[4] = flags;
  h2::put32(f + 5, (uint32_t)sid);
  return f + h2::FRAME_HEAD;
}

// a header block of ours; the first after the peer shrank its table opens
// with an update of ours to 0 (RFC 7541 4.2)
static void out_headers(Conn* c, int32_t sid, uint8_t flags, const uint8_t* block,
                        size_t n) {
  const size_t upd = c->table_update ? 1 : 0;
  uint8_t* p = out_frame(c, (uint32_t)(n + upd), h2::HEADERS, flags, sid);
  if (upd) *p++ = 0x20;
  memcpy(p, block, n);
  c->table_update = false;
}

static void out_rst(Conn* c, int32_t sid, uint32_t code) {
  h2::put32(out_frame(c, 4, h2::RST_STREAM, 0, sid), code);
  c->acks++;
}

static void out_window_update(Conn* c, int32_t sid, int64_t inc) {
  h2::put32(out_frame(c, 4, h2::WINDOW_UPDATE, 0, sid), (uint32_t)inc);
}

// a connection error: GOAWAY is queued, the caller sends it and closes
static bool out_goaway(Conn* c, uint32_t code) {
  uint8_t* p = out_frame(c, 8, h2::GOAWAY, 0, 0);
  h2::put32(p, (uint32_t)c->last_sid);
  h2::put32(p + 4, code);
  return false;
}

// a stream error: RST_STREAM, and the stream is gone
static void reset_stream(Conn* c, StreamSt* st, uint32_t code) {
  out_rst(c, st->sid, code);
  c->streams.erase(st);
}

// the held DATA of an answer as far as the windows and the peer's frame
// size let it go, then its trailers; true once the answer is whole (the
// stream is gone then)
static bool flush_stream(Conn* c, StreamSt* st) {
  while (!st->held.empty()) {
    const int64_t can = std::min({(int64_t)st->held.size(), c->send_window,
                                  st->send_window, (int64_t)c->peer_max_frame});
    if (can <= 0) return false;
    memcpy(out_frame(c, (uint32_t)can, h2::DATA, 0, st->sid), st->held.data(), (size_t)can);
    st->held.erase(0, (size_t)can);
    c->send_window -= can;
    st->send_window -= can;
  }
  out_headers(c, st->sid, h2::END_HEADERS | h2::END_STREAM,
              ANSWER.tail + h2::FRAME_HEAD, sizeof ANSWER.tail - h2::FRAME_HEAD);
  c->streams.erase(st);
  return true;
}

static void flush_held(Conn* c) {
  size_t kept = 0;
  for (int32_t sid : c->held) {
    StreamSt* st = c->streams.find(sid);  // gone: the peer reset it
    if (st && !flush_stream(c, st)) c->held[kept++] = sid;
  }
  c->held.resize(kept);
}

// msg: CheckResponse payload.  The answer goes out whole when the windows
// take it and no size update is owed: the constant bytes around the message,
// the stream id patched into the three frame heads.  Else HEADERS now and
// the DATA held until a WINDOW_UPDATE lets it go.  An answer for a stream the
// peer reset (Envoy does at its ext_authz timeout) is dropped here.
static void submit_grpc_response(Conn* c, int32_t sid, const std::string& msg) {
  StreamSt* st = c->streams.find(sid);
  if (st == nullptr || st->answered) return;
  const size_t n = msg.size();
  const int64_t dlen = (int64_t)(5 + n);
  if (!c->table_update && dlen <= c->send_window && dlen <= st->send_window &&
      dlen <= (int64_t)c->peer_max_frame) {
    c->send_window -= dlen;
    const size_t at = c->outbuf.size();
    c->outbuf.append((const char*)ANSWER.head, sizeof ANSWER.head);
    c->outbuf.append(msg);
    c->outbuf.append((const char*)ANSWER.tail, sizeof ANSWER.tail);
    uint8_t* o = (uint8_t*)&c->outbuf[at];
    uint8_t* d = o + AnswerBytes::DATA_AT;
    h2::put32(o + 5, (uint32_t)sid);
    h2::put24(d, (uint32_t)dlen);
    h2::put32(d + 5, (uint32_t)sid);
    h2::put32(d + h2::FRAME_HEAD + 1, (uint32_t)n);
    h2::put32(d + h2::FRAME_HEAD + 5 + n + 5, (uint32_t)sid);
    c->streams.erase(st);
    return;
  }
  out_headers(c, sid, h2::END_HEADERS, RESP_BLOCK, sizeof RESP_BLOCK);
  st->answered = true;
  uint8_t pfx[5] = {0};
  h2::put32(pfx + 1, (uint32_t)n);
  st->held.assign((const char*)pfx, 5);
  st->held.append(msg);
  if (!flush_stream(c, st)) c->held.push_back(sid);
}

// trailers-only gRPC error (no message body)
static void submit_grpc_error(Conn* c, int32_t sid, int code) {
  StreamSt* st = c->streams.find(sid);
  if (st == nullptr || st->answered) return;
  uint8_t block[sizeof RESP_BLOCK + sizeof STATUS_NAME + 12];
  memcpy(block, RESP_BLOCK, sizeof RESP_BLOCK);
  memcpy(block + sizeof RESP_BLOCK, STATUS_NAME, sizeof STATUS_NAME);
  uint8_t* v = block + sizeof RESP_BLOCK + sizeof STATUS_NAME;
  const int vn = snprintf((char*)v + 1, 11, "%d", code);
  v[0] = (uint8_t)vn;
  out_headers(c, sid, h2::END_HEADERS | h2::END_STREAM, block,
              sizeof RESP_BLOCK + sizeof STATUS_NAME + 1 + (size_t)vn);
  c->streams.erase(st);
}

// ---- fast-lane encode -----------------------------------------------------

static inline void put_id(Snapshot* s, char* base, int64_t idx, int32_t v) {
  if (s->elem16) ((int16_t*)base)[idx] = (int16_t)v;
  else ((int32_t*)base)[idx] = v;
}

// the DFA leaves of config `ci` that read `attr`, over a value past the
// device's byte tensor (exact: the DFA is length-agnostic, same tables, host
// scan).  One pass over the value: every live DFA takes the same byte before
// the next byte is read, so that their table loads (each table is its own
// St x 256 bytes of a store far larger than the cache) are in flight
// together and not one after another; a DFA leaves the live set in a state
// that absorbs, where its verdict is settled.  Each DFA's cpu_dense column
// is written from its last state's accept bit.  `Next` is the tables' next-
// state type: uint8_t, or uint16_t for a store of more than 256 states.
template <typename Next>
static void scan_overflow_as(Server* S, Snapshot* s, size_t ci, int32_t attr,
                             const char* p, size_t n, uint8_t* cpu_dense) {
  ScanLane* lanes = s->scan_lanes.data();
  const size_t St = (size_t)s->dfa_S;
  const uint8_t first = n ? (uint8_t)p[0] : 0;
  int live = 0;
  for (const DfaRef& d : s->cfg_dfas[ci]) {
    if (d.attr != attr) continue;
    ScanLane& l = lanes[live++];
    l.trans = s->dfa_trans.data() + (size_t)d.row * St * 256 * sizeof(Next);
    l.flags = s->dfa_flags.data() + (size_t)d.row * St;
    l.col = d.col;
    l.state = 0;
    __builtin_prefetch(l.trans + first * sizeof(Next));
    __builtin_prefetch(l.flags);
  }
  S->clk.rows[ROW_OVF_DFAS].bump((uint64_t)live);
  uint64_t loads = 0;
  for (size_t i = 0; i < n && live > 0; ++i) {
    const uint8_t b = (uint8_t)p[i];
    loads += (uint64_t)live;
    for (int k = 0; k < live;) {
      ScanLane& l = lanes[k];
      const uint32_t next =
          reinterpret_cast<const Next*>(l.trans)[(size_t)l.state * 256 + b];
      const uint8_t f = l.flags[next];
      if (f & DFA_ABSORBS) {
        cpu_dense[l.col] = f & DFA_ACCEPTS;
        l = lanes[--live];  // not yet advanced by this byte: k stays
      } else {
        l.state = next;
        ++k;
      }
    }
  }
  S->clk.rows[ROW_OVF_LOADS].bump(loads);
  for (int k = 0; k < live; ++k)
    cpu_dense[lanes[k].col] = lanes[k].flags[lanes[k].state] & DFA_ACCEPTS;
}

static void scan_overflow(Server* S, Snapshot* s, size_t ci, int32_t attr,
                          const char* p, size_t n, uint8_t* cpu_dense) {
  if (s->dfa_state_bytes == 2)
    scan_overflow_as<uint16_t>(S, s, ci, attr, p, n, cpu_dense);
  else
    scan_overflow_as<uint8_t>(S, s, ci, attr, p, n, cpu_dense);
}

static void render_i64(int64_t v, std::string& out) {
  char buf[24];
  int n = snprintf(buf, sizeof buf, "%lld", (long long)v);
  out.assign(buf, (size_t)n);
}

// mirror of evaluators/credentials.py AuthCredentials.extract
// (ref pkg/auth/credentials.go:62-75); false → credential not found
static bool extract_cred(const CredSource& fc, const ReqView& rv, std::string& cred) {
  const size_t kl = fc.cred_key.size();
  switch (fc.cred_kind) {
    case 1: {  // authorization header: "<key_selector> <cred>"
      const PbView* h = map_get(rv.headers, "authorization", 13);
      if (!h) return false;
      if (h->n < kl + 1 || memcmp(h->p, fc.cred_key.data(), kl) != 0 ||
          h->p[kl] != ' ')
        return false;
      cred.assign(h->p + kl + 1, h->n - kl - 1);
      return true;
    }
    case 2: {  // custom header (name pre-lowercased in Python)
      const PbView* h = map_get(rv.headers, fc.cred_key.data(), kl);
      if (!h) return false;
      cred.assign(h->p, h->n);
      return true;
    }
    case 3: {  // cookie: split on ';', strip, "<key>=<cred>"
      const PbView* h = map_get(rv.headers, "cookie", 6);
      if (!h) return false;
      const char* p = h->p;
      const char* end = p + h->n;
      while (p < end) {
        const char* semi = (const char*)memchr(p, ';', (size_t)(end - p));
        const char* pe = semi ? semi : end;
        const char* a = p;
        const char* b = pe;
        while (a < b && isspace((unsigned char)*a)) ++a;
        while (b > a && isspace((unsigned char)b[-1])) --b;
        if ((size_t)(b - a) >= kl + 1 && memcmp(a, fc.cred_key.data(), kl) == 0 &&
            a[kl] == '=') {
          cred.assign(a + kl + 1, (size_t)(b - a) - kl - 1);
          return true;
        }
        if (!semi) break;
        p = semi + 1;
      }
      return false;
    }
    case 5: {  // client certificate (mTLS): the raw forwarded PEM is the key
      if (!rv.source_cert.set || rv.source_cert.n == 0) return false;
      cred.assign(rv.source_cert.p, rv.source_cert.n);
      return true;
    }
    case 4: {  // query param in the raw path: [?&]<key>=([^&]*)
      if (!rv.path.set) return false;
      const char* p = rv.path.p;
      const size_t n = rv.path.n;
      for (size_t i = 0; i + kl + 2 <= n; ++i) {
        if ((p[i] == '?' || p[i] == '&') &&
            memcmp(p + i + 1, fc.cred_key.data(), kl) == 0 && p[i + 1 + kl] == '=') {
          const char* vs = p + i + 2 + kl;
          const char* ve = (const char*)memchr(vs, '&', (size_t)(p + n - vs));
          cred.assign(vs, ve ? (size_t)(ve - vs) : (size_t)(p + n - vs));
          return true;
        }
      }
      return false;
    }
  }
  return false;
}

// encode one request into row b of the filling slot; returns false when the
// request needs the slow lane after all (odd path shapes).  `extra` carries
// the per-credential K_CONST plan variant (API-key identity), if any.
static bool encode_fast(Server* S, Snapshot* snap, Slot& sl, int b,
                        const FastConfig& fc, const std::vector<FastPlan>* extra,
                        const ReqView& rv) {
  // pre-split path once if any plan needs url_path/query (urlsplit parity
  // only holds for origin-form paths; anything else → slow lane)
  PbView url_path, qpart;
  if (fc.needs_split) {
    if (!rv.path.set || rv.path.n == 0 || rv.path.p[0] != '/') return false;
    const char* p = rv.path.p;
    const char* end = p + rv.path.n;
    const char* q = (const char*)memchr(p, '?', rv.path.n);
    const char* h = (const char*)memchr(p, '#', rv.path.n);
    const char* path_end = end;
    if (h && (!q || h < q)) { path_end = h; q = nullptr; }
    else if (q) path_end = q;
    if (q) {
      const char* qe = h ? h : end;
      qpart.p = q + 1; qpart.n = (size_t)(qe - q - 1); qpart.set = true;
    }
    url_path.p = p; url_path.n = (size_t)(path_end - p); url_path.set = true;
  }

  const int A = snap->A, K = snap->K, NB = snap->NB, DVB = snap->DVB;
  // the request's row along the flattened [B, S] axis: all writes land in
  // its owning shard's slice (other shards keep the zeroed EMPTY encoding)
  const int64_t bs = (int64_t)b * snap->S + fc.shard;
  const int64_t meta0 = (int64_t)fc.shard * A;  // per-shard metadata base
  std::string tmp;
  const std::vector<FastPlan>* lists[2] = {&fc.plans, extra};
  for (int li = 0; li < 2; ++li) {
  if (lists[li] == nullptr) continue;
  for (const FastPlan& pl : *lists[li]) {
    const int32_t attr = pl.attr;
    int32_t vid;
    const char* vp = nullptr;
    size_t vn = 0;
    bool missing = false;
    if (pl.kind == K_CONST) {
      vid = pl.const_vid;
      missing = pl.const_missing;
      vp = pl.const_bytes.data(); vn = pl.const_bytes.size();
    } else {
      switch (pl.kind) {
        case K_METHOD:   vp = rv.method.p;   vn = rv.method.n; break;
        case K_PATH:     vp = rv.path.p;     vn = rv.path.n; break;
        case K_HOST:     vp = rv.host.p;     vn = rv.host.n; break;
        case K_SCHEME:   vp = rv.scheme.p;   vn = rv.scheme.n; break;
        case K_PROTOCOL: vp = rv.protocol.p; vn = rv.protocol.n; break;
        case K_FRAGMENT: vp = rv.fragment.p; vn = rv.fragment.n; break;
        case K_URL_PATH: vp = url_path.p;    vn = url_path.n; break;
        case K_QUERY:
          // wellknown: split.query or http.query
          if (qpart.set && qpart.n) { vp = qpart.p; vn = qpart.n; }
          else { vp = rv.query.p; vn = rv.query.n; }
          break;
        case K_SIZE:
          render_i64(rv.size, tmp);
          vp = tmp.data(); vn = tmp.size();
          break;
        case K_HEADER: {
          const PbView* h = map_get(rv.headers, pl.key.data(), pl.key.size());
          if (h) { vp = h->p; vn = h->n; } else missing = true;
          break;
        }
        case K_CTX_EXT: {
          const PbView* h = map_get(rv.ctx_ext, pl.key.data(), pl.key.size());
          if (h) { vp = h->p; vn = h->n; } else missing = true;
          break;
        }
        default: return false;
      }
      if (vp == nullptr) vn = 0;
      vid = missing ? snap->interner->lookup("", 0) : snap->interner->lookup(vp, vn);
    }
    put_id(snap, sl.attrs_val, bs * A + attr, vid);
    int32_t mslot = snap->attr_member_slot[meta0 + attr];
    if (mslot >= 0) {
      if (pl.kind == K_CONST) {
        for (size_t k = 0; k < pl.const_members.size() && (int)k < K; ++k)
          put_id(snap, sl.members, (bs * snap->M + mslot) * K + k,
                 pl.const_members[k]);
      } else if (!missing) {
        put_id(snap, sl.members, (bs * snap->M + mslot) * K, vid);
      }
    }
    int32_t bslot = snap->attr_byte_slot_v[meta0 + attr];
    if (bslot >= 0) {
      if (pl.kind != K_CONST && vn && memchr(vp, 0, vn) != nullptr)
        return false;  // NUL: byte 0 is the DFA pad identity — Python regex
                       // lane is the only exact evaluator (slow lane)
      const size_t ci = (size_t)fc.shard * snap->G + fc.row;
      if (ci >= snap->cfg_dfas.size()) return false;
      const uint32_t n_dfas = snap->cfg_slot_dfas[ci * NB + bslot];
      bool ovf = pl.kind == K_CONST ? pl.const_byte_ovf : (int)vn > fc.dvb;
      if (ovf) {
        sl.byte_ovf[bs * NB + bslot] = 1;
        sl.dfa_bytes[2 * b + 1] += n_dfas * (uint32_t)(missing ? 0 : vn);
        S->n_dfa_ovf.fetch_add(1, std::memory_order_relaxed);
        S->fill_row_ovf = true;
        // exact host evaluation of every DFA leaf of this config reading
        // this attr (the DFA is length-agnostic; only the device tensor is
        // fixed-width)
        const char* sp = missing ? "" : vp;
        size_t sn = missing ? 0 : vn;
        S->clk.stamp(PH_ENCODE);
        scan_overflow(S, snap, ci, attr, sp, sn, sl.cpu_dense + bs * snap->C);
        S->clk.stamp(PH_OVF_SCAN);
      } else if (vn) {
        memcpy(sl.attr_bytes + (bs * NB + bslot) * DVB, vp, vn);
        if (vn > sl.byte_used[b]) sl.byte_used[b] = (uint16_t)vn;
        sl.dfa_bytes[2 * b] += n_dfas * (uint32_t)vn;
      }
    }
  }
  }
  sl.config_id[b] = fc.row;
  if (sl.shard_of) sl.shard_of[b] = fc.shard;
  return true;
}

// zero row b of the filling slot (arrays may hold a previous batch's rows);
// zeroes ALL S shard slices — non-owning shards must present the EMPTY
// encoding so their verdict contributions stay masked out
static void zero_row(Snapshot* snap, Slot& sl, int b) {
  const int A = snap->A * snap->S, M = snap->M, K = snap->K,
            C = snap->C * snap->S, NB = snap->NB * snap->S,
            DVB = snap->DVB;
  const int MK = M * K * snap->S;
  const int es = snap->elem16 ? 2 : 4;
  // attrs_val ← EMPTY_ID (0), members ← PAD (-3)
  memset(sl.attrs_val + (int64_t)b * A * es, 0, (size_t)A * es);
  if (snap->elem16) {
    int16_t* m = (int16_t*)sl.members + (int64_t)b * MK;
    for (int i = 0; i < MK; ++i) m[i] = -3;
  } else {
    int32_t* m = (int32_t*)sl.members + (int64_t)b * MK;
    for (int i = 0; i < MK; ++i) m[i] = -3;
  }
  memset(sl.cpu_dense + (int64_t)b * C, 0, (size_t)C);
  if (sl.attr_bytes) {
    // the row's last occupant wrote no byte past byte_used[b] in any slot
    const size_t used = sl.byte_used[b];
    uint8_t* row = sl.attr_bytes + (int64_t)b * NB * DVB;
    for (int i = 0; used && i < NB; ++i) memset(row + (size_t)i * DVB, 0, used);
    sl.byte_used[b] = 0;
    sl.dfa_bytes[2 * b] = sl.dfa_bytes[2 * b + 1] = 0;
  }
  if (sl.byte_ovf) memset(sl.byte_ovf + (int64_t)b * NB, 0, (size_t)NB);
  if (sl.shard_of) sl.shard_of[b] = 0;
}

// ---- batching (epoll thread) ----------------------------------------------

static void arm_timer(Server* S) {
  struct itimerspec its;
  memset(&its, 0, sizeof its);
  its.it_value.tv_sec = S->window_us / 1000000;
  its.it_value.tv_nsec = (S->window_us % 1000000) * 1000;
  timerfd_settime(S->tfd, 0, &its, nullptr);
  S->timer_armed = true;
}

static void disarm_timer(Server* S) {
  struct itimerspec its;
  memset(&its, 0, sizeof its);
  timerfd_settime(S->tfd, 0, &its, nullptr);
  S->timer_armed = false;
}

static void maybe_retire_locked(Server* S, std::vector<int64_t>& retired);
static void emit_retired(Server* S, const std::vector<int64_t>& retired);

// The caller stamps the loop clock on both sides: what led here is its own
// phase's, what runs here `cut`'s.
static void flush_batch(Server* S, bool from_timer = false) {
  if (S->fill_slot < 0) {
    disarm_timer(S);
    return;
  }
  std::shared_ptr<Snapshot> snap = S->fill_snap;
  int slot = S->fill_slot, count = S->fill_count;
  const int ovf_rows = S->fill_ovf_rows;
  std::vector<int64_t> retired;
  bool flushed = false;
  int64_t flush_ns = 0, first_ns = 0;
  {
    // fill_slot/fill_snap transitions stay under mu: Python threads read
    // fill_snap in maybe_retire_locked (an unsynchronized shared_ptr
    // write would be a data race)
    std::lock_guard<std::mutex> lk(S->mu);
    if (count == 0) {
      // empty held slot (a swap raced a failed encode): return it so the
      // old snapshot can retire
      snap->free_slots.push_back(slot);
      S->fill_slot = -1;
      S->fill_snap.reset();
      maybe_retire_locked(S, retired);
    } else if (from_timer && count < S->bmax && snap->pending_batches >= 6 &&
               snap == S->cur) {
      // saturated: enough batches already hide the device RTT, and a
      // partial flush would burn a whole slot on a part-filled batch —
      // slot capacity in *requests* collapses and fast traffic spills to
      // the slow lane.  Let the batch keep filling; re-check next window.
      S->clk.count(LC_CUTS_DEFERRED);
    } else {
      snap->slot_count[slot] = count;
      flush_ns = now_mono_ns();
      snap->slot_flush_ns[slot] = flush_ns;
      first_ns = snap->slot_first_ns[slot];
      snap->pending_batches++;
      S->cuts_owed.fetch_add(1, std::memory_order_relaxed);
      S->fill_slot = -1;
      S->fill_count = 0;
      S->fill_ovf_rows = 0;
      S->fill_snap.reset();
      flushed = true;
    }
  }
  emit_retired(S, retired);
  if (flushed || count == 0) {
    disarm_timer(S);
  } else {
    arm_timer(S);  // deferred partial batch: re-check next window
  }
  if (flushed) {
    {
      std::lock_guard<std::mutex> lk(S->batch_mu);
      S->batch_events.push_back(
          {EV_BATCH, snap->id, slot, count, flush_ns, ovf_rows, first_ns});
    }
    S->batch_cv.notify_all();
    S->clk.rows[PH_CUT].bump();
    if (from_timer) S->clk.rows[ROW_CUT_TIMER].bump();
  }
}

// acquire the filling slot for the current snapshot; nullptr when exhausted
// (back-pressure: request stays queued at the socket)
static Slot* ensure_fill(Server* S, std::shared_ptr<Snapshot>& snap_out) {
  std::lock_guard<std::mutex> lk(S->mu);
  std::shared_ptr<Snapshot> cur = S->cur;
  if (!cur || cur->slots.empty()) return nullptr;
  if (S->fill_slot >= 0 && S->fill_snap != cur) {
    // snapshot changed mid-fill: flush the old batch first (outside mu —
    // just mark and let caller retry)
    return nullptr;
  }
  if (S->fill_slot < 0) {
    if (cur->free_slots.empty()) return nullptr;
    S->fill_slot = cur->free_slots.back();
    cur->free_slots.pop_back();
    S->fill_snap = cur;
    S->fill_count = 0;
    S->fill_ovf_rows = 0;
    cur->slot_entries[S->fill_slot].clear();
  }
  snap_out = S->fill_snap;
  return &snap_out->slots[S->fill_slot];
}

// ---- request processing (epoll thread) ------------------------------------

// Host resolution with wildcard walk-up (ref pkg/index/index.go:153-174;
// mirrors index/index.py::_get_node): exact hit first, then "*."-prefixed
// suffixes deepest-first — "*.example.com" matches a.example.com,
// b.a.example.com AND example.com itself — then a bare "*".
static bool resolve_host(Snapshot* snap, const std::string& host, int32_t& out) {
  auto it = snap->host_map.find(host);
  if (it != snap->host_map.end()) { out = it->second; return true; }
  size_t pos = 0;
  std::string cand;
  for (;;) {
    cand.assign("*.");
    cand.append(host, pos, std::string::npos);
    auto w = snap->host_map.find(cand);
    if (w != snap->host_map.end()) { out = w->second; return true; }
    size_t dot = host.find('.', pos);
    if (dot == std::string::npos) break;
    pos = dot + 1;
  }
  auto b = snap->host_map.find("*");
  if (b != snap->host_map.end()) { out = b->second; return true; }
  return false;
}

static void push_slow(Server* S, Conn* c, int32_t stream_id, const char* msg, size_t n) {
  uint64_t id;
  bool shed = false;
  {
    std::lock_guard<std::mutex> lk(S->mu);
    if (S->slow_pending.size() >= S->slow_cap) {
      shed = true;
    } else {
      id = S->next_slow_id++;
      S->slow_pending[id] = {c->id, stream_id};
    }
  }
  if (shed) {
    S->n_slow_shed.fetch_add(1, std::memory_order_relaxed);
    submit_grpc_error(c, stream_id, 8);  // RESOURCE_EXHAUSTED
    return;
  }
  {
    std::lock_guard<std::mutex> lk(S->slow_mu);
    S->slow_q.push_back({id, std::string(msg, n)});
  }
  S->slow_cv.notify_all();
  S->n_slow.fetch_add(1, std::memory_order_relaxed);
}

// record one direct (never-batched) decision's duration for fc_idx
static inline void record_direct_dur(Snapshot* snap, int32_t fc_idx, int64_t t0) {
  if (!snap->fc_durs) return;
  int64_t dur = now_mono_ns() - t0;
  auto* d = &snap->fc_durs[(size_t)fc_idx * DUR_STRIDE];
  d[dur_bucket(dur)].fetch_add(1, std::memory_order_relaxed);
  d[N_DUR_BUCKETS].fetch_add((uint64_t)dur, std::memory_order_relaxed);
}

// body: the gRPC DATA of the request (5-byte prefix and message), where recv
// put it when `inplace`, else gathered in the stream
static void process_check(Server* S, Conn* c, int32_t stream_id, const char* body,
                          size_t body_n, bool inplace) {
  LoopClock& clk = S->clk;
  // the request's arrival, and the loop clock's stamp: what led here was
  // framing, `read`'s.  From here two more stamps a request: where `parse`
  // ends and where `encode` does
  const int64_t t_start = clk.stamp(PH_READ);
  clk.rows[PH_PARSE].bump();
  // every way out ends the phase `ph` names by then (declared before the
  // pin guard: the unpin is that phase's too)
  struct PhaseEnd {
    LoopClock& clk;
    int ph;
    ~PhaseEnd() { clk.stamp(ph); }
  } at_exit{clk, PH_PARSE};
  // a direct answer or the slow lane: `parse` ends, the rest is `other`
  auto direct = [&] {
    clk.stamp(PH_PARSE);
    clk.rows[PH_OTHER].bump();
    at_exit.ph = PH_OTHER;
  };
  if (inplace) clk.rows[ROW_MSG_INPLACE].bump();
  if (body_n < 5) { submit_grpc_error(c, stream_id, 13); return; }
  if (body[0] != 0) { submit_grpc_error(c, stream_id, 12); return; }  // compressed
  const uint32_t mlen = h2::be32((const uint8_t*)body + 1);
  if (body_n < 5 + (size_t)mlen) { submit_grpc_error(c, stream_id, 13); return; }
  const char* msg = body + 5;
  clk.rows[ROW_REQ_BYTES].bump(mlen);

  std::shared_ptr<Snapshot> snap;
  {
    std::lock_guard<std::mutex> lk(S->mu);
    snap = S->cur;
    S->epoll_pin = snap.get();
  }
  // unpin at every exit; a swap may have been waiting on the pin, so run
  // the retire check the moment it clears
  struct PinGuard {
    Server* S;
    ~PinGuard() {
      std::vector<int64_t> retired;
      {
        std::lock_guard<std::mutex> lk(S->mu);
        S->epoll_pin = nullptr;
        maybe_retire_locked(S, retired);
      }
      emit_retired(S, retired);
    }
  } pin_guard{S};
  if (!snap) { direct(); push_slow(S, c, stream_id, msg, mlen); return; }

  ReqView rv;
  if (!parse_check_request(msg, mlen, rv)) {
    S->n_parse_err.fetch_add(1, std::memory_order_relaxed);
    submit_grpc_error(c, stream_id, 13);
    return;
  }
  clk.rows[ROW_REQ_HEADERS].bump(rv.headers.size());
  if (!rv.has_attributes || !rv.has_request || !rv.has_http) {
    S->n_invalid.fetch_add(1, std::memory_order_relaxed);
    direct();
    submit_grpc_response(c, stream_id, snap->invalid_msg);
    return;
  }
  // host: context_extensions["host"] override, then :authority, then
  // port-strip retry (ref pkg/service/auth.go:270-289)
  const PbView* ov = map_get(rv.ctx_ext, "host", 4);
  std::string host = ov ? ov->str() : rv.host.str();
  int32_t fc_idx;
  bool found = resolve_host(snap.get(), host, fc_idx);
  if (!found) {
    size_t colon = host.rfind(':');
    if (colon != std::string::npos)
      found = resolve_host(snap.get(), host.substr(0, colon), fc_idx);
  }
  if (!found) {
    S->n_notfound.fetch_add(1, std::memory_order_relaxed);
    direct();
    submit_grpc_response(c, stream_id, snap->notfound_msg);
    return;
  }
  if (fc_idx < 0) { direct(); push_slow(S, c, stream_id, msg, mlen); return; }
  if (snap->trace_every > 0 &&
      (int64_t)(S->trace_ctr.fetch_add(1, std::memory_order_relaxed) %
                (uint64_t)snap->trace_every) == 0) {
    // sampled: full pipeline + span export in Python
    S->n_trace_sampled.fetch_add(1, std::memory_order_relaxed);
    direct();
    push_slow(S, c, stream_id, msg, mlen);
    return;
  }

  FastConfig& fc = snap->fcs[fc_idx];
  const std::vector<FastPlan>* extra = nullptr;
  // keeps a dyn variant's plan vector alive across encode_fast after the
  // variant lock is released (overwrites/sweeps may drop the map entry)
  std::shared_ptr<const std::vector<FastPlan>> dyn_hold;
  // the winning identity's OK/DENY response overrides (template configs)
  const std::string* ok_override = nullptr;
  std::shared_ptr<const std::string> ok_hold;
  const std::string* deny_override = nullptr;
  std::shared_ptr<const std::string> deny_hold;
  if (!fc.sources.empty()) {
    // identity is an OR over the sources, tried in the pipeline's
    // priority-then-declaration order: the first source whose credential
    // resolves a variant authenticates (its auth.* constants ride along).
    // An extractable dyn credential that misses its cache routes to the
    // slow lane — it may still verify there; a missed STATIC credential
    // (unknown API key) just falls through to the next source.  With no
    // authentication at all, the all-fail template for the observed
    // static-extraction bitmask answers (every per-source failure message
    // is a static string in that case, so the aggregate is too —
    // ref pkg/service/auth_pipeline.go:203-258 + :468-472).
    bool authenticated = false;
    uint32_t extracted_static = 0;
    int static_idx = 0;
    std::string cred;
    for (const CredSource& src : fc.sources) {
      const int bit = src.dyn ? -1 : static_idx++;
      cred.clear();
      if (!extract_cred(src, rv, cred)) continue;
      if (src.dyn) {
        {
          std::lock_guard<std::mutex> vlk(snap->var_mu);
          auto vit = src.dyn_variants.find(cred);
          if (vit != src.dyn_variants.end() &&
              vit->second.exp_ns > now_realtime_ns()) {
            dyn_hold = vit->second.plans;
            extra = dyn_hold.get();
            if (vit->second.ok) {
              ok_hold = vit->second.ok;
              ok_override = ok_hold.get();
            }
            if (vit->second.deny) {
              deny_hold = vit->second.deny;
              deny_override = deny_hold.get();
            }
          }
        }
        if (extra == nullptr) {
          // unknown/expired credential: the slow lane verifies (and
          // registers on success) — full pipeline semantics
          S->n_dyn_miss.fetch_add(1, std::memory_order_relaxed);
          direct();
          push_slow(S, c, stream_id, msg, mlen);
          return;
        }
        S->n_dyn_hit.fetch_add(1, std::memory_order_relaxed);
        authenticated = true;
        break;
      }
      extracted_static |= 1u << bit;
      auto vit = src.variants.find(cred);
      if (vit != src.variants.end()) {
        extra = &src.var_plans[vit->second.idx];
        if (vit->second.ok_idx >= 0)
          ok_override = &src.var_oks[vit->second.ok_idx];
        if (vit->second.deny_idx >= 0)
          deny_override = &src.var_denies[vit->second.deny_idx];
        authenticated = true;
        break;
      }
    }
    if (!authenticated) {
      const bool any_present = extracted_static != 0;
      snap->fc_counts[3 * (size_t)fc_idx + (any_present ? 2 : 1)].fetch_add(
          1, std::memory_order_relaxed);
      S->n_fast.fetch_add(1, std::memory_order_relaxed);
      S->n_unauth.fetch_add(1, std::memory_order_relaxed);
      S->n_denied.fetch_add(1, std::memory_order_relaxed);
      record_direct_dur(snap.get(), fc_idx, t_start);
      direct();
      submit_grpc_response(c, stream_id, fc.unauth_msgs[extracted_static]);
      return;
    }
  }
  if (!fc.has_batch) {
    // identity-only config: authenticated → OK, no kernel involvement
    snap->fc_counts[3 * (size_t)fc_idx].fetch_add(1, std::memory_order_relaxed);
    S->n_fast.fetch_add(1, std::memory_order_relaxed);
    S->n_direct_ok.fetch_add(1, std::memory_order_relaxed);
    S->n_allowed.fetch_add(1, std::memory_order_relaxed);
    record_direct_dur(snap.get(), fc_idx, t_start);
    direct();
    submit_grpc_response(c, stream_id,
                         ok_override ? *ok_override : fc.ok_msg);
    return;
  }
  // the FastConfig and its identity are chosen: `parse` ends, `encode` begins
  clk.stamp(PH_PARSE);
  at_exit.ph = PH_ENCODE;
  // the slow lane after all: what `encode` spent stays its own
  auto to_slow = [&] {
    clk.stamp(PH_ENCODE);
    clk.rows[PH_OTHER].bump();
    at_exit.ph = PH_OTHER;
    push_slow(S, c, stream_id, msg, mlen);
  };
  std::shared_ptr<Snapshot> fsnap;
  Slot* sl = ensure_fill(S, fsnap);
  if (sl == nullptr) {
    // no slot (exhausted or snapshot raced): flush and retry once
    clk.stamp(PH_ENCODE);
    flush_batch(S);
    clk.stamp(PH_CUT);
    sl = ensure_fill(S, fsnap);
    if (sl == nullptr) { to_slow(); return; }
  }
  if (fsnap != snap) {
    // snapshot swapped between lookup and slot acquire: redo via slow lane
    to_slow();
    return;
  }
  int b = S->fill_count;
  zero_row(snap.get(), *sl, b);
  S->fill_row_ovf = false;
  if (!encode_fast(S, snap.get(), *sl, b, fc, extra, rv)) {
    to_slow();
    return;
  }
  if (S->fill_row_ovf) {
    S->fill_ovf_rows++;
    clk.rows[PH_OVF_SCAN].bump();
  }
  if (b == 0) snap->slot_first_ns[S->fill_slot] = t_start;
  snap->slot_entries[S->fill_slot].push_back(
      {c->id, stream_id, fc_idx, t_start, ok_override, std::move(ok_hold),
       deny_override, std::move(deny_hold),
       fc.hybrid ? std::string(msg, mlen) : std::string()});
  S->fill_count++;
  S->n_fast.fetch_add(1, std::memory_order_relaxed);
  clk.rows[PH_ENCODE].bump();
  if (S->fill_count >= S->bmax) {
    clk.stamp(PH_ENCODE);
    flush_batch(S);
    at_exit.ph = PH_CUT;
  } else if (S->fill_count == 1) {
    arm_timer(S);
  }
}

// a whole request: a Check to the fast lane, the rest answered here (the
// loop clock's `other`).  The stream may be gone on return.
static void process_request(Server* S, Conn* c, int32_t stream_id, int kind,
                            bool compressed, const char* body, size_t n, bool inplace) {
  if (kind == SK_CHECK && !compressed) {
    process_check(S, c, stream_id, body, n, inplace);
    return;
  }
  S->clk.stamp(PH_READ);
  if (kind == SK_HEALTH) {
    std::shared_ptr<Snapshot> snap;
    {
      std::lock_guard<std::mutex> lk(S->mu);
      snap = S->cur;
    }
    S->n_health.fetch_add(1, std::memory_order_relaxed);
    submit_grpc_response(c, stream_id, snap ? snap->health_msg : S->health_msg);
  } else {
    submit_grpc_error(c, stream_id, 12);  // compressed Check, or UNIMPLEMENTED
  }
  S->clk.rows[PH_OTHER].bump();
  S->clk.stamp(PH_OTHER);
}

// ---- the framer's read side (epoll thread only) ---------------------------
// The frames are walked where recv put them.  Of a request's headers only
// `:path` and `grpc-encoding` are read (the rest are decoded, to keep the
// HPACK table in step, and dropped); its message, whole in one DATA frame
// with END_STREAM, is handed on in place, or gathered once in the stream
// when it spans frames.  A connection error queues GOAWAY and returns false.

static int path_kind(const char* v, size_t n) {
  static const char kCheck[] = "/envoy.service.auth.v3.Authorization/Check";
  static const char kHealth[] = "/grpc.health.v1.Health/Check";
  if (n == sizeof(kCheck) - 1 && memcmp(v, kCheck, n) == 0) return SK_CHECK;
  if (n == sizeof(kHealth) - 1 && memcmp(v, kHealth, n) == 0) return SK_HEALTH;
  return SK_OTHER;
}

// the gathered message, out of the stream (which its answer may erase)
static void end_gathered(Server* S, Conn* c, StreamSt* st) {
  if (st->too_big) {
    submit_grpc_error(c, st->sid, 8);  // RESOURCE_EXHAUSTED
    return;
  }
  std::string body;
  body.swap(st->body);
  process_request(S, c, st->sid, st->kind, st->compressed, body.data(), body.size(), false);
}

static bool on_header_block(Server* S, Conn* c, int32_t sid, uint8_t flags,
                            const uint8_t* block, size_t n) {
  int kind = SK_UNSET;
  bool compressed = false;
  const bool decoded = c->hpack.decode(
      block, n, [&](const char* nm, size_t nl, const char* v, size_t vl) {
        if (nl == 5 && memcmp(nm, ":path", 5) == 0)
          kind = path_kind(v, vl);
        else if (nl == 13 && memcmp(nm, "grpc-encoding", 13) == 0)
          compressed = !(vl == 8 && memcmp(v, "identity", 8) == 0);
      });
  if (!decoded) return out_goaway(c, h2::COMPRESSION_ERROR);
  StreamSt* st = c->streams.find(sid);
  if (st == nullptr) {
    if (sid <= c->last_sid) {
      // trailers of a stream closed here are dropped; a block without
      // END_STREAM opens a stream below the last (RFC 9113 5.1.1)
      return (flags & h2::END_STREAM) ? true : out_goaway(c, h2::PROTOCOL_ERROR);
    }
    c->last_sid = sid;
    if (c->streams.n >= h2::MAX_STREAMS) {
      out_rst(c, sid, h2::REFUSED_STREAM);
      return true;
    }
    st = c->streams.insert(sid);
    st->kind = (uint8_t)kind;
    st->compressed = compressed;
    st->send_window = c->peer_window;
    if (flags & h2::END_STREAM) {
      st->half_closed = true;
      process_request(S, c, sid, kind, compressed, nullptr, 0, false);
    }
    return true;
  }
  // trailers: they end the request
  if (st->half_closed) {
    reset_stream(c, st, h2::STREAM_CLOSED);
  } else if (!(flags & h2::END_STREAM)) {
    reset_stream(c, st, h2::PROTOCOL_ERROR);
  } else {
    st->half_closed = true;
    end_gathered(S, c, st);
  }
  return true;
}

static bool on_data(Server* S, Conn* c, uint8_t flags, int32_t sid, const uint8_t* p,
                    uint32_t n) {
  if (sid == 0) return out_goaway(c, h2::PROTOCOL_ERROR);
  // flow control counts the whole payload, padding too
  if ((int64_t)n > c->recv_window) return out_goaway(c, h2::FLOW_CONTROL_ERROR);
  c->recv_window -= n;
  if (c->recv_window <= h2::CONN_WINDOW / 2) {
    out_window_update(c, 0, h2::CONN_WINDOW - c->recv_window);
    c->recv_window = h2::CONN_WINDOW;
  }
  size_t m = n;
  if (flags & h2::PADDED) {
    if (m < 1 || p[0] >= m) return out_goaway(c, h2::PROTOCOL_ERROR);
    m -= 1 + p[0];
    p++;
  }
  StreamSt* st = c->streams.find(sid);
  if (st == nullptr)  // idle: an error; closed here: dropped
    return sid > c->last_sid ? out_goaway(c, h2::PROTOCOL_ERROR) : true;
  if (st->half_closed) {
    reset_stream(c, st, h2::STREAM_CLOSED);
    return true;
  }
  st->recv_used += n;
  if (st->recv_used > h2::STREAM_WINDOW) {
    reset_stream(c, st, h2::FLOW_CONTROL_ERROR);
    return true;
  }
  if ((flags & h2::END_STREAM) && st->body.empty() && !st->too_big) {
    st->half_closed = true;
    process_request(S, c, sid, st->kind, st->compressed, (const char*)p, m, true);
    return true;
  }
  if (st->too_big || st->body.size() + m > h2::MAX_MESSAGE) {
    st->too_big = true;
    std::string().swap(st->body);
  } else {
    st->body.append((const char*)p, m);
  }
  if (flags & h2::END_STREAM) {
    st->half_closed = true;
    end_gathered(S, c, st);
  } else if (st->recv_used >= h2::STREAM_WINDOW / 2) {
    out_window_update(c, sid, st->recv_used);
    st->recv_used = 0;
  }
  return true;
}

static bool on_settings(Conn* c, uint8_t flags, int32_t sid, const uint8_t* p, uint32_t n) {
  if (sid != 0) return out_goaway(c, h2::PROTOCOL_ERROR);
  if (flags & h2::ACK) return n == 0 ? true : out_goaway(c, h2::FRAME_SIZE_ERROR);
  if (n % 6) return out_goaway(c, h2::FRAME_SIZE_ERROR);
  for (uint32_t i = 0; i < n; i += 6) {
    const uint16_t id = (uint16_t)((p[i] << 8) | p[i + 1]);
    const uint32_t v = h2::be32(p + i + 2);
    switch (id) {
      case h2::S_HEADER_TABLE_SIZE:
        // our encoder's table is never used: a shrink is met with a size
        // update to 0 at the start of our next header block
        if (v < c->enc_table) {
          c->enc_table = 0;
          c->table_update = true;
        }
        break;
      case h2::S_ENABLE_PUSH:
        if (v > 1) return out_goaway(c, h2::PROTOCOL_ERROR);
        break;
      case h2::S_INITIAL_WINDOW_SIZE: {
        if (v > h2::MAX_WINDOW) return out_goaway(c, h2::FLOW_CONTROL_ERROR);
        const int64_t delta = (int64_t)v - c->peer_window;
        c->peer_window = v;
        for (StreamSt& st : c->streams.slots) {
          if (st.sid == 0) continue;
          st.send_window += delta;
          if (st.send_window > h2::MAX_WINDOW) return out_goaway(c, h2::FLOW_CONTROL_ERROR);
        }
        break;
      }
      case h2::S_MAX_FRAME_SIZE:
        if (v < h2::MAX_FRAME || v > 0xffffff) return out_goaway(c, h2::PROTOCOL_ERROR);
        c->peer_max_frame = v;
        break;
      default:
        break;  // MAX_CONCURRENT_STREAMS (we open none), MAX_HEADER_LIST_SIZE, unknown
    }
  }
  out_frame(c, 0, h2::SETTINGS, h2::ACK, 0);
  c->acks++;
  flush_held(c);
  return true;
}

static bool on_frame(Server* S, Conn* c, uint8_t type, uint8_t flags, int32_t sid,
                     const uint8_t* p, uint32_t n) {
  if (c->cont_sid) {
    if (type != h2::CONTINUATION || sid != c->cont_sid)
      return out_goaway(c, h2::PROTOCOL_ERROR);
    if (c->cont_block.size() + n > h2::MAX_HEADER_BLOCK)
      return out_goaway(c, h2::ENHANCE_YOUR_CALM);
    c->cont_block.append((const char*)p, n);
    if (!(flags & h2::END_HEADERS)) return true;
    c->cont_sid = 0;
    std::string block;
    block.swap(c->cont_block);
    return on_header_block(S, c, sid, c->cont_flags, (const uint8_t*)block.data(),
                           block.size());
  }
  if (!c->settings) {  // the client's preface ends with a SETTINGS
    if (type != h2::SETTINGS || (flags & h2::ACK)) return out_goaway(c, h2::PROTOCOL_ERROR);
    c->settings = true;
  }
  switch (type) {
    case h2::DATA:
      return on_data(S, c, flags, sid, p, n);
    case h2::HEADERS: {
      if (sid == 0 || !(sid & 1)) return out_goaway(c, h2::PROTOCOL_ERROR);
      size_t m = n, pad = 0;
      if (flags & h2::PADDED) {
        if (m < 1) return out_goaway(c, h2::FRAME_SIZE_ERROR);
        pad = *p++;
        m--;
      }
      if (flags & h2::PRIORITY_FLAG) {  // its dependency and weight are ignored
        if (m < 5) return out_goaway(c, h2::FRAME_SIZE_ERROR);
        p += 5;
        m -= 5;
      }
      if (pad > m) return out_goaway(c, h2::PROTOCOL_ERROR);
      m -= pad;
      if (!(flags & h2::END_HEADERS)) {
        c->cont_sid = sid;
        c->cont_flags = flags;
        c->cont_block.assign((const char*)p, m);
        return true;
      }
      return on_header_block(S, c, sid, flags, p, m);
    }
    case h2::PRIORITY:
      if (sid == 0) return out_goaway(c, h2::PROTOCOL_ERROR);
      if (n != 5) {
        StreamSt* st = c->streams.find(sid);
        if (st) reset_stream(c, st, h2::FRAME_SIZE_ERROR);
        else out_rst(c, sid, h2::FRAME_SIZE_ERROR);
      }
      return true;
    case h2::RST_STREAM: {
      if (sid == 0 || sid > c->last_sid) return out_goaway(c, h2::PROTOCOL_ERROR);
      if (n != 4) return out_goaway(c, h2::FRAME_SIZE_ERROR);
      StreamSt* st = c->streams.find(sid);
      if (st) c->streams.erase(st);  // an answer that comes later is dropped
      return true;
    }
    case h2::SETTINGS:
      return on_settings(c, flags, sid, p, n);
    case h2::PING:
      if (sid != 0) return out_goaway(c, h2::PROTOCOL_ERROR);
      if (n != 8) return out_goaway(c, h2::FRAME_SIZE_ERROR);
      if (!(flags & h2::ACK)) {
        memcpy(out_frame(c, 8, h2::PING, h2::ACK, 0), p, 8);
        c->acks++;
      }
      return true;
    case h2::GOAWAY:  // the peer opens no more streams: those open are answered
      if (sid != 0) return out_goaway(c, h2::PROTOCOL_ERROR);
      if (n < 8) return out_goaway(c, h2::FRAME_SIZE_ERROR);
      return true;
    case h2::WINDOW_UPDATE: {
      if (n != 4) return out_goaway(c, h2::FRAME_SIZE_ERROR);
      const int64_t inc = h2::be32(p) & 0x7fffffff;
      if (sid == 0) {
        if (inc == 0) return out_goaway(c, h2::PROTOCOL_ERROR);
        c->send_window += inc;
        if (c->send_window > h2::MAX_WINDOW) return out_goaway(c, h2::FLOW_CONTROL_ERROR);
      } else {
        StreamSt* st = c->streams.find(sid);
        if (st == nullptr)
          return sid > c->last_sid ? out_goaway(c, h2::PROTOCOL_ERROR) : true;
        if (inc == 0) {
          reset_stream(c, st, h2::PROTOCOL_ERROR);
          return true;
        }
        st->send_window += inc;
        if (st->send_window > h2::MAX_WINDOW) {
          reset_stream(c, st, h2::FLOW_CONTROL_ERROR);
          return true;
        }
      }
      flush_held(c);
      return true;
    }
    case h2::PUSH_PROMISE:  // a client sends none
    case h2::CONTINUATION:  // none is expected
      return out_goaway(c, h2::PROTOCOL_ERROR);
    default:
      return true;  // unknown types are ignored (RFC 9113 4.1, 5.5)
  }
}

// walk the frames recv left in the connection's buffer, then carry the tail
// of a partial frame to its front; false on a connection error
static bool h2_walk(Server* S, Conn* c) {
  uint8_t* buf = c->rbuf.get();
  const size_t len = c->rlen;
  size_t pos = 0;
  if (!c->preface) {
    if (memcmp(buf, h2::PREFACE, std::min(len, h2::PREFACE_LEN)) != 0)
      return out_goaway(c, h2::PROTOCOL_ERROR);
    if (len < h2::PREFACE_LEN) return true;
    c->preface = true;
    pos = h2::PREFACE_LEN;
  }
  while (len - pos >= h2::FRAME_HEAD) {
    const uint8_t* f = buf + pos;
    const uint32_t flen = h2::be24(f);
    if (flen > h2::MAX_FRAME) return out_goaway(c, h2::FRAME_SIZE_ERROR);
    if (len - pos < h2::FRAME_HEAD + flen) break;
    const int32_t sid = (int32_t)(h2::be32(f + 5) & 0x7fffffff);
    if (!on_frame(S, c, f[3], f[4], sid, f + h2::FRAME_HEAD, flen)) return false;
    if (c->acks > h2::MAX_ACKS) return out_goaway(c, h2::ENHANCE_YOUR_CALM);
    pos += h2::FRAME_HEAD + flen;
  }
  if (pos) memmove(buf, buf + pos, len - pos);
  c->rlen = len - pos;
  return true;
}

// what the server says first: SETTINGS (the stream limit, a 1 MB stream
// window), then the connection window widened to 1 GB
static void out_server_preface(Conn* c) {
  uint8_t* p = out_frame(c, 12, h2::SETTINGS, 0, 0);
  p[0] = 0;
  p[1] = h2::S_MAX_CONCURRENT_STREAMS;
  h2::put32(p + 2, h2::MAX_STREAMS);
  p[6] = 0;
  p[7] = h2::S_INITIAL_WINDOW_SIZE;
  h2::put32(p + 8, (uint32_t)h2::STREAM_WINDOW);
  out_window_update(c, 0, h2::CONN_WINDOW - h2::DEFAULT_WINDOW);
}

// ---- epoll loop -----------------------------------------------------------

static void conn_close(Server* S, Conn* c) {
  {
    std::lock_guard<std::mutex> lk(S->mu);
    S->conns.erase(c->id);
  }
  epoll_ctl(S->epfd, EPOLL_CTL_DEL, c->fd, nullptr);
  close(c->fd);
  delete c;
}

// one send of what the connection's out buffer holds; what the kernel does
// not take waits for EPOLLOUT, and past OUT_CAP unsent the connection is not
// read until it is under again (its open streams then bound what more can be
// queued).  The loop clock's `write`: the caller stamps it when the pump
// returns; the syscall's own time is the row `send`.
static bool conn_pump(Server* S, Conn* c) {
  const size_t left = c->outbuf.size() - c->out_off;
  if (left) {
    const int64_t t0 = now_mono_ns();
    const ssize_t w = send(c->fd, c->outbuf.data() + c->out_off, left, MSG_NOSIGNAL);
    S->clk.rows[ROW_SEND].add(now_mono_ns() - t0);
    S->clk.rows[ROW_SEND].bump();
    S->clk.rows[PH_WRITE].bump();
    if (w < 0) {
      if (errno != EAGAIN && errno != EWOULDBLOCK) return false;
      S->clk.count(LC_SEND_BLOCKED);
    } else if ((size_t)w == left) {
      c->outbuf.clear();
      c->out_off = 0;
      c->acks = 0;
    } else if (w > 0) {
      c->out_off += (size_t)w;
      c->acks = 0;
      if (c->out_off >= c->outbuf.size() / 2) {
        c->outbuf.erase(0, c->out_off);
        c->out_off = 0;
      }
    }
  }
  const size_t unsent = c->outbuf.size() - c->out_off;
  const uint32_t want = (unsent <= h2::OUT_CAP ? EPOLLIN : 0) | (unsent ? EPOLLOUT : 0);
  if (want != c->events) {
    struct epoll_event ev;
    ev.events = want;
    ev.data.u32 = c->id;
    epoll_ctl(S->epfd, EPOLL_CTL_MOD, c->fd, &ev);
    c->events = want;
  }
  return true;
}

static void accept_conns(Server* S) {
  for (;;) {
    int fd = accept4(S->listen_fd, nullptr, nullptr, SOCK_NONBLOCK);
    if (fd < 0) break;
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
    Conn* c = new Conn();
    c->fd = fd;
    out_server_preface(c);
    {
      std::lock_guard<std::mutex> lk(S->mu);
      c->id = S->next_conn_id++;
      S->conns[c->id] = c;
    }
    S->n_conns.fetch_add(1, std::memory_order_relaxed);
    struct epoll_event ev;
    ev.events = EPOLLIN;
    ev.data.u32 = c->id;
    epoll_ctl(S->epfd, EPOLL_CTL_ADD, fd, &ev);
    S->clk.rows[PH_OTHER].bump();
    S->clk.stamp(PH_OTHER);
    conn_pump(S, c);
    S->clk.stamp(PH_WRITE);
  }
  S->clk.stamp(PH_OTHER);
}

static void drain_done(Server* S) {
  std::deque<Done> q;
  {
    std::lock_guard<std::mutex> lk(S->done_mu);
    q.swap(S->done_q);
  }
  std::vector<Conn*> touched;
  uint64_t answers = 0, timed = 0, late_sum = 0, late_max = 0;
  // a cut's answers come in runs of one connection's; connections are
  // opened and closed on this thread alone, so a lookup holds for the drain
  uint32_t last_id = 0;
  Conn* c = nullptr;
  for (Done& d : q) {
    if (d.conn_id != last_id) {  // ids start at 1
      std::lock_guard<std::mutex> lk(S->mu);
      auto it = S->conns.find(d.conn_id);
      c = it == S->conns.end() ? nullptr : it->second;
      last_id = d.conn_id;
    }
    if (!c) continue;
    if (d.grpc_status) submit_grpc_error(c, d.stream_id, d.grpc_status);
    else submit_grpc_response(c, d.stream_id, d.msg);
    answers++;
    if (d.t_done) {
      const int64_t late = now_mono_ns() - d.t_done;
      S->stage_respond[stage_bucket(late)].fetch_add(1, std::memory_order_relaxed);
      const uint64_t v = late > 0 ? (uint64_t)late : 0;
      timed++;
      late_sum += v;
      if (v > late_max) late_max = v;
    }
    if (std::find(touched.begin(), touched.end(), c) == touched.end())
      touched.push_back(c);
  }
  // the respond histogram's exact sum, once a drain
  if (timed) S->clk.rows[ROW_REQ_RESPOND].add_shared(timed, late_sum, late_max);
  S->clk.rows[PH_RESPOND].bump(answers);
  S->clk.stamp(PH_RESPOND);
  for (Conn* c : touched) {
    const bool alive = conn_pump(S, c);
    S->clk.stamp(PH_WRITE);
    if (!alive) {
      conn_close(S, c);
      S->clk.rows[PH_OTHER].bump();
      S->clk.stamp(PH_OTHER);
    }
  }
}

static void epoll_loop(Server* S) {
  LoopClock& clk = S->clk;
  struct epoll_event evs[64];
  clk.mark.store(now_mono_ns(), std::memory_order_relaxed);
  while (S->running.load(std::memory_order_relaxed)) {
    // no stamp on the way in: what lies between a wake's last stamp and
    // epoll_wait's return is `idle`'s, and the wake's turn ended at that stamp
    const bool owed = S->fill_count > 0 ||
                      S->cuts_owed.load(std::memory_order_relaxed) > 0;
    const int64_t slept = clk.mark.load(std::memory_order_relaxed);
    int n = epoll_wait(S->epfd, evs, 64, 100);
    const int64_t woke = clk.stamp(PH_IDLE);
    clk.rows[PH_IDLE].bump();
    const uint64_t req0 = clk.rows[PH_PARSE].count.load(std::memory_order_relaxed);
    const uint64_t ans0 = clk.rows[PH_RESPOND].count.load(std::memory_order_relaxed);
    for (int i = 0; i < n; ++i) {
      uint32_t id = evs[i].data.u32;
      if (id == 0xFFFFFFFFu) {  // listen fd
        accept_conns(S);
        continue;
      }
      if (id == 0xFFFFFFFEu) {  // eventfd: completions pending
        uint64_t v;
        while (read(S->evfd, &v, 8) == 8) {}
        clk.rows[PH_OTHER].bump();
        clk.stamp(PH_OTHER);
        drain_done(S);
        continue;
      }
      if (id == 0xFFFFFFFDu) {  // timerfd: micro-batch window expired
        uint64_t v;
        while (read(S->tfd, &v, 8) == 8) {}
        clk.rows[PH_OTHER].bump();
        clk.stamp(PH_OTHER);
        flush_batch(S, /*from_timer=*/true);
        clk.stamp(PH_CUT);
        continue;
      }
      Conn* c;
      {
        std::lock_guard<std::mutex> lk(S->mu);
        auto it = S->conns.find(id);
        c = it == S->conns.end() ? nullptr : it->second;
      }
      if (!c) continue;
      bool dead = false;
      bool closing = false;  // a connection error: its GOAWAY goes, then the socket
      if (evs[i].events & (EPOLLHUP | EPOLLERR)) dead = true;
      if (!dead && (evs[i].events & EPOLLIN)) {
        // not past OUT_CAP unsent: a walk, or a drain since epoll_wait
        // returned, may pass it
        while (c->outbuf.size() - c->out_off <= h2::OUT_CAP) {
          // one recv into the buffer, past the tail the last walk carried
          const size_t room = RBUF_BYTES - c->rlen;
          const int64_t t0 = now_mono_ns();
          const ssize_t r = recv(c->fd, c->rbuf.get() + c->rlen, room, 0);
          clk.rows[ROW_RECV].add(now_mono_ns() - t0);
          clk.rows[ROW_RECV].bump();
          clk.rows[PH_READ].bump();
          if (r > 0) {
            c->rlen += (size_t)r;
            if (!h2_walk(S, c)) { closing = true; break; }
            if ((size_t)r < room) break;
          } else if (r == 0) { dead = true; break; }
          else {
            if (errno == EAGAIN || errno == EWOULDBLOCK) break;
            dead = true; break;
          }
        }
      }
      clk.stamp(PH_READ);
      if (!dead) {
        dead = !conn_pump(S, c) || closing;
        clk.stamp(PH_WRITE);
      }
      if (dead) {
        conn_close(S, c);
        clk.rows[PH_OTHER].bump();
        clk.stamp(PH_OTHER);
      }
    }
    clk.end_turn(woke, woke - slept, owed, n > 0 ? n : 0,
                 clk.rows[PH_PARSE].count.load(std::memory_order_relaxed) - req0,
                 clk.rows[PH_RESPOND].count.load(std::memory_order_relaxed) - ans0);
  }
  // shutdown: close all conns, notify waiters
  std::vector<Conn*> all;
  {
    std::lock_guard<std::mutex> lk(S->mu);
    for (auto& kv : S->conns) all.push_back(kv.second);
    S->conns.clear();
  }
  for (Conn* c : all) {
    epoll_ctl(S->epfd, EPOLL_CTL_DEL, c->fd, nullptr);
    close(c->fd);
    delete c;
  }
  {
    std::lock_guard<std::mutex> lk(S->batch_mu);
    S->batch_events.push_back({EV_STOPPED, 0, 0, 0, 0, 0, 0});
  }
  S->batch_cv.notify_all();
  S->slow_cv.notify_all();
}

// ---- control-plane entry points (called from Python with GIL held, except
// the waits which release it in pymod) ---------------------------------------

static int server_start(Server* S) {
  S->listen_fd = socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
  if (S->listen_fd < 0) return -2;
  int one = 1;
  setsockopt(S->listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof one);
  struct sockaddr_in addr;
  memset(&addr, 0, sizeof addr);
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(S->any_addr ? INADDR_ANY : INADDR_LOOPBACK);
  addr.sin_port = htons((uint16_t)S->port);
  if (bind(S->listen_fd, (struct sockaddr*)&addr, sizeof addr) < 0 ||
      listen(S->listen_fd, 1024) < 0) {
    close(S->listen_fd);  // error paths must not leak the socket
    S->listen_fd = -1;
    return -3;
  }
  socklen_t alen = sizeof addr;
  getsockname(S->listen_fd, (struct sockaddr*)&addr, &alen);
  S->bound_port = ntohs(addr.sin_port);
  S->epfd = epoll_create1(0);
  S->evfd = eventfd(0, EFD_NONBLOCK);
  S->tfd = timerfd_create(CLOCK_MONOTONIC, TFD_NONBLOCK);
  struct epoll_event ev;
  ev.events = EPOLLIN;
  ev.data.u32 = 0xFFFFFFFFu;
  epoll_ctl(S->epfd, EPOLL_CTL_ADD, S->listen_fd, &ev);
  ev.data.u32 = 0xFFFFFFFEu;
  epoll_ctl(S->epfd, EPOLL_CTL_ADD, S->evfd, &ev);
  ev.data.u32 = 0xFFFFFFFDu;
  epoll_ctl(S->epfd, EPOLL_CTL_ADD, S->tfd, &ev);
  S->running.store(true);
  S->thr = std::thread(epoll_loop, S);
  return 0;
}

static void server_stop(Server* S) {
  if (!S->running.exchange(false)) return;
  if (S->thr.joinable()) S->thr.join();
  if (S->listen_fd >= 0) close(S->listen_fd);
  if (S->epfd >= 0) close(S->epfd);
  if (S->evfd >= 0) close(S->evfd);
  if (S->tfd >= 0) close(S->tfd);
}

static void wake_epoll(Server* S) {
  uint64_t one = 1;
  ssize_t r = write(S->evfd, &one, 8);
  (void)r;
}

// retire check: emit SNAP_RETIRED for non-current snapshots with no pending
// batches, and ERASE them from the registry — retired snapshots hold
// dangling pointers (numpy slots, interner) once Python frees its side, and
// an append-only map would leak a full corpus copy per reconcile.
// Call under S->mu.
static void maybe_retire_locked(Server* S, std::vector<int64_t>& retired) {
  for (auto it = S->snaps.begin(); it != S->snaps.end();) {
    Snapshot* sn = it->second.get();
    if (it->second != S->cur && sn->pending_batches == 0 && sn != S->epoll_pin &&
        (S->fill_snap == nullptr || S->fill_snap.get() != sn)) {
      // undrained direct-decision counters survive retirement in the
      // leftover map so no metric increment is lost
      for (size_t f = 0; sn->fc_counts && f < sn->fcs.size(); ++f) {
        uint64_t ok = sn->fc_counts[3 * f].exchange(0);
        uint64_t mi = sn->fc_counts[3 * f + 1].exchange(0);
        uint64_t inv = sn->fc_counts[3 * f + 2].exchange(0);
        if (ok | mi | inv) {
          auto& agg = S->fc_leftover[sn->fcs[f].ns + '\x1f' + sn->fcs[f].name];
          agg[0] += ok;
          agg[1] += mi;
          agg[2] += inv;
        }
      }
      // same for undrained duration-histogram buckets
      for (size_t f = 0; sn->fc_durs && f < sn->fcs.size(); ++f) {
        uint64_t any = 0;
        uint64_t vals[DUR_STRIDE];
        for (int k = 0; k < DUR_STRIDE; ++k)
          any |= (vals[k] = sn->fc_durs[f * DUR_STRIDE + k].exchange(0));
        if (any) {
          auto& agg = S->dur_leftover[sn->fcs[f].ns + '\x1f' + sn->fcs[f].name];
          for (int k = 0; k < DUR_STRIDE; ++k) agg[k] += vals[k];
        }
      }
      retired.push_back(sn->id);
      it = S->snaps.erase(it);
    } else {
      ++it;
    }
  }
}

// drain per-authconfig direct-decision counters (all live snapshots + the
// leftovers of retired ones) into `out`, keyed ns+'\x1f'+name
static void drain_fc_counts(
    Server* S, std::unordered_map<std::string, std::array<uint64_t, 3>>& out) {
  std::lock_guard<std::mutex> lk(S->mu);
  for (auto& kv : S->snaps) {
    Snapshot* sn = kv.second.get();
    for (size_t f = 0; sn->fc_counts && f < sn->fcs.size(); ++f) {
      uint64_t ok = sn->fc_counts[3 * f].exchange(0);
      uint64_t mi = sn->fc_counts[3 * f + 1].exchange(0);
      uint64_t inv = sn->fc_counts[3 * f + 2].exchange(0);
      if (ok | mi | inv) {
        auto& agg = out[sn->fcs[f].ns + '\x1f' + sn->fcs[f].name];
        agg[0] += ok;
        agg[1] += mi;
        agg[2] += inv;
      }
    }
  }
  for (auto& kv : S->fc_leftover) {
    auto& agg = out[kv.first];
    agg[0] += kv.second[0];
    agg[1] += kv.second[1];
    agg[2] += kv.second[2];
  }
  S->fc_leftover.clear();
}

// drain per-authconfig duration histograms (live snapshots + retired
// leftovers) into `out`, keyed ns+'\x1f'+name → [15 buckets, sum_ns]
static void drain_durations(
    Server* S, std::unordered_map<std::string, std::array<uint64_t, DUR_STRIDE>>& out) {
  std::lock_guard<std::mutex> lk(S->mu);
  for (auto& kv : S->snaps) {
    Snapshot* sn = kv.second.get();
    for (size_t f = 0; sn->fc_durs && f < sn->fcs.size(); ++f) {
      uint64_t any = 0;
      uint64_t vals[DUR_STRIDE];
      for (int k = 0; k < DUR_STRIDE; ++k)
        any |= (vals[k] = sn->fc_durs[f * DUR_STRIDE + k].exchange(0));
      if (any) {
        auto& agg = out[sn->fcs[f].ns + '\x1f' + sn->fcs[f].name];
        for (int k = 0; k < DUR_STRIDE; ++k) agg[k] += vals[k];
      }
    }
  }
  for (auto& kv : S->dur_leftover) {
    auto& agg = out[kv.first];
    for (int k = 0; k < DUR_STRIDE; ++k) agg[k] += kv.second[k];
  }
  S->dur_leftover.clear();
}

static void emit_retired(Server* S, const std::vector<int64_t>& retired) {
  if (retired.empty()) return;
  {
    std::lock_guard<std::mutex> lk(S->batch_mu);
    for (int64_t id : retired) S->batch_events.push_back({EV_SNAP_RETIRED, id, 0, 0, 0, 0, 0});
  }
  S->batch_cv.notify_all();
}

static void complete_batch(Server* S, int64_t snap_id, int slot, const uint8_t* verdict) {
  std::shared_ptr<Snapshot> snap;
  std::vector<Entry> entries;
  {
    std::lock_guard<std::mutex> lk(S->mu);
    auto it = S->snaps.find(snap_id);
    if (it == S->snaps.end()) return;
    snap = it->second;
    entries.swap(snap->slot_entries[slot]);
  }
  uint64_t allowed = 0, handed_off = 0;
  const int64_t t_now = now_mono_ns();
  const int64_t t_flush = snap->slot_flush_ns[slot];
  const int exec_b = stage_bucket(t_now - t_flush);
  // hybrid kernel-PASS entries: collected under mu, enqueued to the slow
  // lane after (push ordering mirrors push_slow: mu for slow_pending,
  // then slow_mu — never nested)
  struct Handoff { uint32_t conn_id; int32_t stream_id; std::string raw; };
  std::vector<Handoff> handoffs;
  std::deque<Done> dones;
  {
    std::lock_guard<std::mutex> lk(S->mu);
    for (size_t i = 0; i < entries.size(); ++i) {
      Entry& e = entries[i];
      const FastConfig& fc = snap->fcs[e.fc];
      bool ok = verdict[i] != 0;
      if (ok && fc.hybrid) {
        handed_off++;
        handoffs.push_back({e.conn_id, e.stream_id, std::move(e.raw)});
        continue;
      }
      allowed += ok;
      dones.push_back(
          {e.conn_id, e.stream_id,
           ok ? (e.ok_msg ? *e.ok_msg : fc.ok_msg)
              : (e.deny_msg ? *e.deny_msg : fc.deny_msg),
           0, t_now});
    }
    snap->free_slots.push_back(slot);
    snap->pending_batches--;
    S->cuts_owed.fetch_sub(1, std::memory_order_relaxed);
  }
  for (Handoff& h : handoffs) {
    uint64_t id = 0;
    bool shed = false;
    {
      std::lock_guard<std::mutex> lk(S->mu);
      if (S->slow_pending.size() >= S->slow_cap) {
        shed = true;
      } else {
        id = S->next_slow_id++;
        S->slow_pending[id] = {h.conn_id, h.stream_id};
      }
    }
    if (shed) {
      dones.push_back({h.conn_id, h.stream_id, std::string(), 8, 0});
      S->n_slow_shed.fetch_add(1, std::memory_order_relaxed);
      continue;
    }
    {
      std::lock_guard<std::mutex> lk(S->slow_mu);
      S->slow_q.push_back({id, std::move(h.raw)});
    }
    S->n_slow.fetch_add(1, std::memory_order_relaxed);
  }
  if (!handoffs.empty()) S->slow_cv.notify_all();
  if (!dones.empty()) {
    std::lock_guard<std::mutex> lk(S->done_mu);
    for (Done& d : dones) S->done_q.push_back(std::move(d));
  }
  // per-request on-box stages + the duration series the pipeline observes
  // (ref pkg/service/auth_pipeline.go:26-36): all clocked here, on the box.
  // Hybrid handoffs skip the duration series — the Python pipeline they
  // continue into observes them itself (no double counting)
  uint64_t wait_sum = 0, wait_max = 0;
  for (size_t i = 0; i < entries.size(); ++i) {
    const Entry& e = entries[i];
    const int64_t waited = t_flush - e.t_enq;
    S->stage_wait[stage_bucket(waited)].fetch_add(1, std::memory_order_relaxed);
    S->stage_exec[exec_b].fetch_add(1, std::memory_order_relaxed);
    const uint64_t w = waited > 0 ? (uint64_t)waited : 0;
    wait_sum += w;
    if (w > wait_max) wait_max = w;
    if (verdict[i] != 0 && snap->fcs[e.fc].hybrid) continue;
    if (snap->fc_durs) {
      int64_t dur = t_now - e.t_enq;
      auto* d = &snap->fc_durs[(size_t)e.fc * DUR_STRIDE];
      d[dur_bucket(dur)].fetch_add(1, std::memory_order_relaxed);
      d[N_DUR_BUCKETS].fetch_add((uint64_t)dur, std::memory_order_relaxed);
    }
  }
  // the two histograms' exact sums, once a cut
  const uint64_t exec_ns = t_now > t_flush ? (uint64_t)(t_now - t_flush) : 0;
  S->clk.rows[ROW_REQ_WAIT].add_shared(entries.size(), wait_sum, wait_max);
  S->clk.rows[ROW_REQ_EXEC].add_shared(entries.size(), exec_ns * entries.size(), exec_ns);
  S->n_hybrid.fetch_add(handed_off, std::memory_order_relaxed);
  S->n_allowed.fetch_add(allowed, std::memory_order_relaxed);
  S->n_denied.fetch_add(entries.size() - handed_off - allowed,
                        std::memory_order_relaxed);
  std::vector<int64_t> retired;
  {
    std::lock_guard<std::mutex> lk(S->mu);
    maybe_retire_locked(S, retired);
  }
  emit_retired(S, retired);
  wake_epoll(S);
}

// register (or refresh) a runtime plan variant for one credential — the
// slow lane calls this after a successful token verification.  Overwrites
// swap the shared_ptr (a mid-request reader holds its own reference), so
// stale plan vectors free as soon as the last reader drops.  Returns false
// when the snapshot is gone (stale registration: harmless no-op) or the
// cap is hit.
static bool add_variant(Server* S, int64_t snap_id, int32_t fc_idx,
                        int32_t src_idx, std::string cred,
                        std::vector<FastPlan> plans, std::string ok_bytes,
                        std::string deny_bytes, int64_t exp_ns) {
  std::shared_ptr<Snapshot> snap;
  {
    std::lock_guard<std::mutex> lk(S->mu);
    auto it = S->snaps.find(snap_id);
    if (it == S->snaps.end()) return false;
    snap = it->second;
  }
  if (fc_idx < 0 || (size_t)fc_idx >= snap->fcs.size()) return false;
  FastConfig& fc = snap->fcs[fc_idx];
  if (src_idx < 0 || (size_t)src_idx >= fc.sources.size()) return false;
  CredSource& src = fc.sources[src_idx];
  if (!src.dyn) return false;
  auto sp = std::make_shared<const std::vector<FastPlan>>(std::move(plans));
  std::shared_ptr<const std::string> ok;
  if (!ok_bytes.empty())
    ok = std::make_shared<const std::string>(std::move(ok_bytes));
  std::shared_ptr<const std::string> deny;
  if (!deny_bytes.empty())
    deny = std::make_shared<const std::string>(std::move(deny_bytes));
  {
    std::lock_guard<std::mutex> vlk(snap->var_mu);
    auto it = src.dyn_variants.find(cred);
    if (it == src.dyn_variants.end() &&
        src.dyn_variants.size() >= DYN_VARIANT_CAP) {
      // sweep expired entries once; if still full, the slow lane keeps
      // serving this token (correct, just not fast)
      int64_t now = now_realtime_ns();
      for (auto sit = src.dyn_variants.begin(); sit != src.dyn_variants.end();)
        sit = sit->second.exp_ns <= now ? src.dyn_variants.erase(sit)
                                        : std::next(sit);
      if (src.dyn_variants.size() >= DYN_VARIANT_CAP) return false;
      it = src.dyn_variants.end();
    }
    if (it != src.dyn_variants.end())
      it->second = {std::move(sp), exp_ns, std::move(ok), std::move(deny)};
    else
      src.dyn_variants.emplace(
          std::move(cred),
          CredSource::DynVar{std::move(sp), exp_ns, std::move(ok),
                             std::move(deny)});
  }
  S->n_dyn_add.fetch_add(1, std::memory_order_relaxed);
  return true;
}

static void complete_slow(Server* S, uint64_t req_id, const char* msg, size_t n,
                          int grpc_status) {
  SlowPending sp;
  {
    std::lock_guard<std::mutex> lk(S->mu);
    auto it = S->slow_pending.find(req_id);
    if (it == S->slow_pending.end()) return;
    sp = it->second;
    S->slow_pending.erase(it);
  }
  bool was_empty;
  {
    std::lock_guard<std::mutex> lk(S->done_mu);
    was_empty = S->done_q.empty();
    S->done_q.push_back({sp.conn_id, sp.stream_id, std::string(msg, n),
                         grpc_status, now_mono_ns()});
  }
  // coalesce wakes: drain_done swaps the WHOLE queue under done_mu, so a
  // non-empty observation means a wake is already owed — the eventfd
  // write per completion was a measurable share of the slow lane's budget
  if (was_empty) wake_epoll(S);
}

// batch form: the Python slow lane buffers finished responses and a
// dedicated completer thread lands N of them in two lock rounds + at most
// one wake — per-response mutex/wake traffic was ~35µs of contended wall
// on the asyncio thread
struct SlowDone { uint64_t req_id; std::string msg; int grpc_status; };

static void complete_slow_many(Server* S, std::vector<SlowDone>& items) {
  std::deque<Done> dones;
  const int64_t t_now = now_mono_ns();
  {
    std::lock_guard<std::mutex> lk(S->mu);
    for (SlowDone& sd : items) {
      auto it = S->slow_pending.find(sd.req_id);
      if (it == S->slow_pending.end()) continue;
      dones.push_back({it->second.conn_id, it->second.stream_id,
                       std::move(sd.msg), sd.grpc_status, t_now});
      S->slow_pending.erase(it);
    }
  }
  if (dones.empty()) return;
  bool was_empty;
  {
    std::lock_guard<std::mutex> lk(S->done_mu);
    was_empty = S->done_q.empty();
    for (Done& d : dones) S->done_q.push_back(std::move(d));
  }
  if (was_empty) wake_epoll(S);
}

}  // namespace fe
