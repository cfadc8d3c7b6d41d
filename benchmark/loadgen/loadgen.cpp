// HTTP/2 gRPC load generator of the benchmark: a copy of native/loadgen.cpp
// (raw frames, prebaked requests, one thread), extended with what a judged
// run needs and the original lacks:
//
//   - the order in which rows are sent is a file (u32 LE row indices, wrapped),
//     so one generator serves a cycle, a Zipf draw or any later mix;
//   - an open loop: a file of due times (f64 LE seconds from the start); each
//     request is sent when it is due whatever is still in flight, and timed
//     from the instant it was due;
//   - one record per request: row, the answer received (the CheckResponse's
//     status code, -1 for a trailers-only gRPC error, -2 for a reset stream,
//     -3 for none), due, sent and done times in ms from the window's start;
//   - a fixed window [warm, warm + seconds) and a grace after it in which
//     nothing is sent and outstanding answers are waited for.
//
// Usage: loadgen <host> <port> <warm_s> <seconds> <depth> <conns> <grace_s>
//   stdin: three sections, each [u64 LE byte count][bytes]:
//     payloads  repeated [u32 big-endian length][CheckRequest bytes]
//     order     u32 LE row indices
//     due       f64 LE seconds; empty: closed loop, depth x conns in flight
//   stdout: the records (nothing touches the disk); stderr: one JSON line.

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <sys/socket.h>
#include <time.h>
#include <unistd.h>

#include <math.h>

#include <algorithm>
#include <string>
#include <unordered_map>
#include <vector>

static double now_s() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return ts.tv_sec + ts.tv_nsec * 1e-9;
}

static void be24(std::string& s, uint32_t v) {
  s.push_back((char)(v >> 16));
  s.push_back((char)(v >> 8));
  s.push_back((char)v);
}

static void be32(std::string& s, uint32_t v) {
  s.push_back((char)(v >> 24));
  s.push_back((char)(v >> 16));
  s.push_back((char)(v >> 8));
  s.push_back((char)v);
}

// one request's frames with the two stream-id offsets to patch
struct Baked {
  std::string bytes;
  size_t sid_off1, sid_off2;
};

static Baked bake(const std::string& msg) {
  // HPACK block: literals without indexing, no huffman
  std::string hp;
  hp.push_back((char)0x83);  // :method POST (static 3)
  hp.push_back((char)0x86);  // :scheme http (static 6)
  static const char kPath[] = "/envoy.service.auth.v3.Authorization/Check";
  hp.push_back((char)0x04);  // literal w/o indexing, name = static 4 (:path)
  hp.push_back((char)(sizeof(kPath) - 1));
  hp.append(kPath, sizeof(kPath) - 1);
  hp.push_back((char)0x01);  // :authority (static 1)
  hp.push_back((char)2);
  hp.append("lg", 2);
  hp.push_back((char)0x0f);  // content-type (static 31 = 15 + 16)
  hp.push_back((char)0x10);
  hp.push_back((char)16);
  hp.append("application/grpc", 16);
  hp.push_back((char)0x00);  // te: trailers (new name)
  hp.push_back((char)2);
  hp.append("te", 2);
  hp.push_back((char)8);
  hp.append("trailers", 8);

  Baked b;
  // HEADERS frame
  be24(b.bytes, (uint32_t)hp.size());
  b.bytes.push_back((char)0x01);  // type HEADERS
  b.bytes.push_back((char)0x04);  // END_HEADERS
  b.sid_off1 = b.bytes.size();
  be32(b.bytes, 0);
  b.bytes.append(hp);
  // DATA frame: 5-byte gRPC prefix + message, END_STREAM
  uint32_t dlen = 5 + (uint32_t)msg.size();
  be24(b.bytes, dlen);
  b.bytes.push_back((char)0x00);  // type DATA
  b.bytes.push_back((char)0x01);  // END_STREAM
  b.sid_off2 = b.bytes.size();
  be32(b.bytes, 0);
  b.bytes.push_back((char)0);     // uncompressed
  be32(b.bytes, (uint32_t)msg.size());
  b.bytes.append(msg);
  return b;
}

// one request, as the harness reads it back (20 bytes, little endian)
struct Record {
  uint32_t row;
  int32_t code;     // CheckResponse.status.code; -1 gRPC error, -2 reset, -3 no answer
  float due_ms;     // all three from the window's start
  float sent_ms;
  float done_ms;    // NaN while unanswered
};

struct StreamSt {
  uint32_t rec;
  bool has_msg = false;
  std::string data;  // DATA payload: 5-byte gRPC prefix + CheckResponse
};

struct ConnSt {
  int fd = -1;
  std::string out;
  size_t out_off = 0;
  // reader state machine
  uint8_t hdr[9];
  int hdr_got = 0;
  uint32_t frame_len = 0;
  uint8_t frame_type = 0, frame_flags = 0;
  int32_t frame_sid = 0;
  uint32_t payload_left = 0;
  std::vector<uint8_t> payload;  // kept for SETTINGS, PING and DATA
  bool collect_payload = false;
  int32_t next_sid = 1;
  int in_flight = 0;
  std::unordered_map<int32_t, StreamSt> streams;
  bool dead = false;
};

static std::vector<Record> g_rec;
static double g_t0 = 0;  // the window's start
static uint64_t g_in_flight = 0;

static uint64_t varint(const uint8_t*& p, const uint8_t* end) {
  uint64_t v = 0;
  for (int shift = 0; p < end && shift < 64; shift += 7) {
    uint8_t b = *p++;
    v |= (uint64_t)(b & 0x7f) << shift;
    if (!(b & 0x80)) break;
  }
  return v;
}

// field `want` (length-delimited) of a message, or the varint field when
// `as_varint`; returns false when absent.  Enough protobuf to read
// CheckResponse.status (1) . code (1).
static bool pb_field(const uint8_t* p, const uint8_t* end, uint32_t want, bool as_varint,
                     const uint8_t** sub, const uint8_t** sub_end, uint64_t* val) {
  while (p < end) {
    uint64_t key = varint(p, end);
    uint32_t field = (uint32_t)(key >> 3), wt = (uint32_t)(key & 7);
    if (wt == 0) {
      uint64_t v = varint(p, end);
      if (field == want && as_varint) { *val = v; return true; }
    } else if (wt == 2) {
      uint64_t n = varint(p, end);
      if (n > (uint64_t)(end - p)) return false;
      if (field == want && !as_varint) { *sub = p; *sub_end = p + n; return true; }
      p += n;
    } else if (wt == 1) {
      p += 8;
    } else if (wt == 5) {
      p += 4;
    } else {
      return false;
    }
  }
  return false;
}

static int32_t response_code(const std::string& data) {
  if (data.size() < 5) return -1;
  const uint8_t* p = (const uint8_t*)data.data() + 5;
  const uint8_t* end = (const uint8_t*)data.data() + data.size();
  const uint8_t *s = nullptr, *se = nullptr;
  uint64_t v = 0;
  if (!pb_field(p, end, 1, false, &s, &se, &v)) return 0;  // no status: code 0
  if (!pb_field(s, se, 1, true, &s, &se, &v)) return 0;    // proto3 omits a 0
  return (int32_t)v;
}

static void stream_done(ConnSt& c, int32_t sid, bool reset) {
  auto it = c.streams.find(sid);
  if (it == c.streams.end()) return;
  Record& r = g_rec[it->second.rec];
  r.done_ms = (float)((now_s() - g_t0) * 1e3);
  r.code = reset ? -2 : (it->second.has_msg ? response_code(it->second.data) : -1);
  c.streams.erase(it);
  c.in_flight--;
  g_in_flight--;
}

static void handle_frame(ConnSt& c) {
  switch (c.frame_type) {
    case 0x04:  // SETTINGS
      if (!(c.frame_flags & 0x01)) {
        static const char ack[] = {0, 0, 0, 0x04, 0x01, 0, 0, 0, 0};
        c.out.append(ack, 9);
      }
      break;
    case 0x06:  // PING
      if (!(c.frame_flags & 0x01) && c.payload.size() == 8) {
        std::string f;
        be24(f, 8);
        f.push_back((char)0x06);
        f.push_back((char)0x01);
        be32(f, 0);
        f.append((const char*)c.payload.data(), 8);
        c.out.append(f);
      }
      break;
    case 0x01:  // HEADERS (response or trailers)
      if (c.frame_flags & 0x01) stream_done(c, c.frame_sid, false);
      break;
    case 0x00: {  // DATA
      auto it = c.streams.find(c.frame_sid);
      if (it != c.streams.end() && it->second.data.size() < 65536) {
        it->second.has_msg = true;
        it->second.data.append((const char*)c.payload.data(), c.payload.size());
      }
      if (c.frame_flags & 0x01) stream_done(c, c.frame_sid, false);
      break;
    }
    case 0x03:  // RST_STREAM
      stream_done(c, c.frame_sid, true);
      break;
    case 0x07:  // GOAWAY
      c.dead = true;
      break;
    default:
      break;
  }
}

static void feed(ConnSt& c, const uint8_t* p, size_t n) {
  while (n) {
    if (c.payload_left) {
      size_t take = n < c.payload_left ? n : c.payload_left;
      if (c.collect_payload) c.payload.insert(c.payload.end(), p, p + take);
      c.payload_left -= (uint32_t)take;
      p += take;
      n -= take;
      if (c.payload_left == 0) handle_frame(c);
      continue;
    }
    size_t need = 9 - c.hdr_got;
    size_t take = n < need ? n : need;
    memcpy(c.hdr + c.hdr_got, p, take);
    c.hdr_got += (int)take;
    p += take;
    n -= take;
    if (c.hdr_got < 9) return;
    c.hdr_got = 0;
    c.frame_len = ((uint32_t)c.hdr[0] << 16) | ((uint32_t)c.hdr[1] << 8) | c.hdr[2];
    c.frame_type = c.hdr[3];
    c.frame_flags = c.hdr[4];
    c.frame_sid = (int32_t)(((uint32_t)c.hdr[5] << 24) | ((uint32_t)c.hdr[6] << 16) |
                            ((uint32_t)c.hdr[7] << 8) | c.hdr[8]) & 0x7fffffff;
    c.payload.clear();
    c.collect_payload =
        (c.frame_type == 0x04 || c.frame_type == 0x06 || c.frame_type == 0x00);
    c.payload_left = c.frame_len;
    if (c.payload_left == 0) handle_frame(c);
  }
}

static bool read_section(std::string& out) {
  uint8_t lb[8];
  if (fread(lb, 1, 8, stdin) != 8) return false;
  uint64_t n = 0;
  for (int i = 7; i >= 0; --i) n = (n << 8) | lb[i];
  out.resize((size_t)n);
  return n == 0 || fread(&out[0], 1, (size_t)n, stdin) == (size_t)n;
}

static void send_one(ConnSt& c, const Baked& b, uint32_t row, double due, double now) {
  size_t base = c.out.size();
  c.out.append(b.bytes);
  uint32_t sid = (uint32_t)c.next_sid;
  uint8_t* p1 = (uint8_t*)&c.out[base + b.sid_off1];
  uint8_t* p2 = (uint8_t*)&c.out[base + b.sid_off2];
  p1[0] = (uint8_t)(sid >> 24); p1[1] = (uint8_t)(sid >> 16);
  p1[2] = (uint8_t)(sid >> 8);  p1[3] = (uint8_t)sid;
  p2[0] = (uint8_t)(sid >> 24); p2[1] = (uint8_t)(sid >> 16);
  p2[2] = (uint8_t)(sid >> 8);  p2[3] = (uint8_t)sid;
  Record r;
  r.row = row;
  r.code = -3;
  r.due_ms = (float)((due - g_t0) * 1e3);
  r.sent_ms = (float)((now - g_t0) * 1e3);
  r.done_ms = NAN;
  StreamSt st;
  st.rec = (uint32_t)g_rec.size();
  g_rec.push_back(r);
  c.streams.emplace((int32_t)sid, std::move(st));
  c.next_sid += 2;
  c.in_flight++;
  g_in_flight++;
}

int main(int argc, char** argv) {
  if (argc < 8) {
    fprintf(stderr,
            "usage: loadgen <host> <port> <warm_s> <seconds> <depth> <conns> <grace_s>\n");
    return 2;
  }
  const char* host = argv[1];
  int port = atoi(argv[2]);
  double warmup = atof(argv[3]);
  double seconds = atof(argv[4]);
  int depth = atoi(argv[5]);
  int nconns = atoi(argv[6]);
  double grace = atof(argv[7]);

  std::string pay, ord, du;
  if (!read_section(pay) || !read_section(ord) || !read_section(du)) {
    fprintf(stderr, "short input\n");
    return 2;
  }
  std::vector<Baked> baked;
  for (size_t off = 0; off + 4 <= pay.size();) {
    const uint8_t* lb = (const uint8_t*)pay.data() + off;
    uint32_t len = ((uint32_t)lb[0] << 24) | ((uint32_t)lb[1] << 16) |
                   ((uint32_t)lb[2] << 8) | lb[3];
    if (off + 4 + len > pay.size()) break;
    baked.push_back(bake(pay.substr(off + 4, len)));
    off += 4 + (size_t)len;
  }
  if (baked.empty()) { fprintf(stderr, "no payloads\n"); return 2; }
  std::vector<uint32_t> order(ord.size() / 4);
  memcpy(order.data(), ord.data(), order.size() * 4);
  if (order.empty()) { fprintf(stderr, "no order\n"); return 2; }
  for (uint32_t row : order)
    if (row >= baked.size()) { fprintf(stderr, "order names row %u\n", row); return 2; }
  std::vector<double> due(du.size() / 8);
  memcpy(due.data(), du.data(), due.size() * 8);
  bool open_loop = !due.empty();
  std::string().swap(pay);
  std::string().swap(ord);
  std::string().swap(du);

  std::vector<ConnSt> conns((size_t)nconns);
  for (ConnSt& c : conns) {
    c.fd = socket(AF_INET, SOCK_STREAM, 0);
    struct sockaddr_in addr;
    memset(&addr, 0, sizeof addr);
    addr.sin_family = AF_INET;
    addr.sin_port = htons((uint16_t)port);
    inet_pton(AF_INET, host, &addr.sin_addr);
    if (connect(c.fd, (struct sockaddr*)&addr, sizeof addr) < 0) {
      perror("connect");
      return 2;
    }
    int one = 1;
    setsockopt(c.fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
    fcntl(c.fd, F_SETFL, O_NONBLOCK);
    c.out = "PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n";
    // SETTINGS: huge initial window, then a huge connection WINDOW_UPDATE —
    // flow control effectively disabled client-side (responses are tiny)
    std::string st;
    be24(st, 12);
    st.push_back((char)0x04);
    st.push_back((char)0x00);
    be32(st, 0);
    st.push_back(0); st.push_back(0x04); be32(st, 0x7fffffff);  // INITIAL_WINDOW_SIZE
    st.push_back(0); st.push_back(0x03); be32(st, 0x7fffffff);  // MAX_CONCURRENT_STREAMS
    c.out.append(st);
    std::string wu;
    be24(wu, 4);
    wu.push_back((char)0x08);
    wu.push_back((char)0x00);
    be32(wu, 0);
    be32(wu, 0x7fffffff - 65535);
    c.out.append(wu);
  }

  g_rec.reserve(1 << 22);
  double t_start = now_s();
  g_t0 = t_start + warmup;
  double t_end = g_t0 + seconds;
  double t_give_up = t_end + grace;
  size_t seq = 0;      // next entry of order (closed) or of due (open)
  size_t rr = 0;       // open loop: next connection, round robin

  std::vector<struct pollfd> pfds((size_t)nconns);
  static uint8_t buf[262144];
  for (;;) {
    double now = now_s();
    if (now >= t_end && (g_in_flight == 0 || now >= t_give_up)) break;

    int wait_ms = 10;
    if (now < t_end) {
      if (open_loop) {
        // everything that is due goes out now, whatever is in flight
        while (seq < due.size() && t_start + due[seq] <= now &&
               t_start + due[seq] < t_end) {
          ConnSt* c = nullptr;
          for (int k = 0; k < nconns && !c; ++k) {
            ConnSt& cand = conns[rr++ % (size_t)nconns];
            if (!cand.dead) c = &cand;
          }
          if (!c) break;
          uint32_t row = order[seq % order.size()];
          send_one(*c, baked[row], row, t_start + due[seq], now);
          seq++;
        }
        if (seq < due.size()) {
          double gap = t_start + due[seq] - now_s();
          wait_ms = gap < 0.001 ? 0 : (gap < 0.010 ? 1 : 10);
        }
      } else {
        // top up each connection's pipeline
        for (ConnSt& c : conns) {
          if (c.dead) continue;
          while (c.in_flight < depth && c.next_sid < 0x7ffffff0 &&
                 c.out.size() - c.out_off < (size_t)4 << 20) {
            uint32_t row = order[seq++ % order.size()];
            double t = now_s();
            send_one(c, baked[row], row, t, t);
          }
        }
      }
    }

    for (int i = 0; i < nconns; ++i) {
      pfds[i].fd = conns[i].fd;
      pfds[i].events = POLLIN;
      if (conns[i].out_off < conns[i].out.size()) pfds[i].events |= POLLOUT;
    }
    poll(pfds.data(), (nfds_t)nconns, wait_ms);
    for (int i = 0; i < nconns; ++i) {
      ConnSt& c = conns[i];
      if (c.dead) continue;
      if (pfds[i].revents & POLLOUT) {
        ssize_t w = send(c.fd, c.out.data() + c.out_off, c.out.size() - c.out_off,
                         MSG_NOSIGNAL);
        if (w > 0) {
          c.out_off += (size_t)w;
          if (c.out_off == c.out.size()) {
            c.out.clear();
            c.out_off = 0;
          } else if (c.out_off > (size_t)1 << 20) {
            c.out.erase(0, c.out_off);
            c.out_off = 0;
          }
        } else if (w < 0 && errno != EAGAIN && errno != EWOULDBLOCK) {
          c.dead = true;
        }
      }
      if (pfds[i].revents & (POLLIN | POLLHUP)) {
        for (;;) {
          ssize_t r = recv(c.fd, buf, sizeof buf, 0);
          if (r > 0) {
            feed(c, buf, (size_t)r);
            if (r < (ssize_t)sizeof buf) break;
          } else if (r == 0) {
            c.dead = true;
            break;
          } else {
            if (errno != EAGAIN && errno != EWOULDBLOCK) c.dead = true;
            break;
          }
        }
      }
    }
  }
  double waited = now_s() - t_end;
  int dead = 0;
  for (ConnSt& c : conns) {
    if (c.dead) dead++;
    close(c.fd);
  }

  size_t wrote = fwrite(g_rec.data(), sizeof(Record), g_rec.size(), stdout);
  if (fflush(stdout) != 0 || wrote != g_rec.size()) { perror("records"); return 2; }
  fprintf(stderr,
          "{\"sent\": %zu, \"unanswered\": %llu, \"t_window_monotonic\": %.6f, "
          "\"warm_s\": %.3f, \"seconds\": %.3f, \"waited_after_close_s\": %.3f, "
          "\"open_loop\": %s, \"dead_conns\": %d, \"conns\": %d, \"depth\": %d}\n",
          g_rec.size(), (unsigned long long)g_in_flight, g_t0, warmup, seconds, waited,
          open_loop ? "true" : "false", dead, nconns, depth);
  return 0;
}
