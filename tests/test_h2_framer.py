"""Conformance of the native front end's HTTP/2 framer (native/frontend.cpp
"The framer", docs/architecture.md "The HTTP/2 framer") on loopback: raw
frames for each part of RFC 9113 and RFC 7541 the server implements or
refuses, and grpcio, a whole HTTP/2 stack of its own (indexing, Huffman
coding, window updates), for many Checks at once."""

from __future__ import annotations

import socket
import struct
import threading
import time

import grpc
import pytest

from authorino_tpu import protos
from authorino_tpu.runtime.native_frontend import NativeFrontend

from test_native_frontend import (REQUESTS, _native_available, build_engine,
                                  make_req, response_key, run_python_server)

pb = protos.external_auth_pb2

pytestmark = pytest.mark.skipif(
    not _native_available(), reason="native frontend unavailable")

CHECK = b"/envoy.service.auth.v3.Authorization/Check"
HEALTH = b"/grpc.health.v1.Health/Check"
PREFACE = b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n"
DATA, HEADERS, PRIORITY, RST_STREAM, SETTINGS = 0, 1, 2, 3, 4
PING, GOAWAY, WINDOW_UPDATE, CONTINUATION = 6, 7, 8, 9
END_STREAM, ACK, END_HEADERS, PADDED, PRIO = 0x1, 0x1, 0x4, 0x8, 0x20
PROTOCOL_ERROR, FRAME_SIZE_ERROR, COMPRESSION_ERROR = 1, 6, 9
RESOURCE_EXHAUSTED = 8  # a gRPC status

# ---------------------------------------------------------------------------
# HPACK, the client's side: an encoder of every representation (RFC 7541 6)
# and the Huffman code (Appendix B), canonical, as its 257 bit lengths
# ---------------------------------------------------------------------------

HUFF_LEN = [
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
]


def _huffman_codes():
    codes, code, prev = [0] * 257, 0, 0
    for sym in sorted(range(257), key=lambda s: (HUFF_LEN[s], s)):
        code <<= HUFF_LEN[sym] - prev
        prev = HUFF_LEN[sym]
        codes[sym] = code
        code += 1
    return codes


HUFF_CODE = _huffman_codes()


def huffman(data: bytes, pad_bit: int = 1) -> bytes:
    acc = nbits = 0
    for b in data:
        acc = (acc << HUFF_LEN[b]) | HUFF_CODE[b]
        nbits += HUFF_LEN[b]
    pad = -nbits % 8
    acc = (acc << pad) | ((1 << pad) - 1 if pad_bit else 0)
    return (acc).to_bytes((nbits + pad) // 8, "big") if nbits else b""


def hp_int(value: int, prefix: int, first: int) -> bytes:
    limit = (1 << prefix) - 1
    if value < limit:
        return bytes([first | value])
    out, value = [first | limit], value - limit
    while value >= 128:
        out.append((value & 0x7f) | 0x80)
        value >>= 7
    return bytes(out + [value])


def hp_str(s: bytes, huff: bool = False) -> bytes:
    if huff:
        h = huffman(s)
        return hp_int(len(h), 7, 0x80) + h
    return hp_int(len(s), 7, 0) + s


def indexed(i: int) -> bytes:
    return hp_int(i, 7, 0x80)


def literal(name, value: bytes, kind: str = "without", huff: bool = False) -> bytes:
    """A literal field (6.2): `kind` is incremental ("with"), "without" or
    "never" indexing; `name` a table index or the name's bytes."""
    first, prefix = {"with": (0x40, 6), "without": (0x00, 4),
                     "never": (0x10, 4)}[kind]
    if isinstance(name, int):
        head = hp_int(name, prefix, first)
    else:
        head = bytes([first]) + hp_str(name, huff)
    return head + hp_str(value, huff)


def size_update(n: int) -> bytes:
    return hp_int(n, 5, 0x20)


def check_block(path: bytes = CHECK) -> bytes:
    """What the benchmark's generator sends: literals, no table, no Huffman."""
    return (indexed(3) + indexed(6) + literal(4, path) + literal(1, b"lg")
            + literal(31, b"application/grpc") + literal(b"te", b"trailers"))


def grpc_msg(req) -> bytes:
    body = req.SerializeToString()
    return b"\x00" + struct.pack(">I", len(body)) + body


def frame(ftype: int, flags: int, sid: int, payload: bytes = b"") -> bytes:
    return (len(payload).to_bytes(3, "big") + bytes([ftype, flags])
            + struct.pack(">I", sid) + payload)


def settings(**entries) -> bytes:
    ids = {"header_table_size": 1, "initial_window_size": 4,
           "max_frame_size": 5, "max_concurrent_streams": 3}
    return frame(SETTINGS, 0, 0, b"".join(
        struct.pack(">HI", ids[k], v) for k, v in entries.items()))


def request(sid: int, req, block: bytes | None = None) -> bytes:
    return (frame(HEADERS, END_HEADERS, sid, block or check_block())
            + frame(DATA, END_STREAM, sid, grpc_msg(req)))


def allow(tag: str):
    return make_req("fast-eq.test", headers={"x-org": "acme", "x-tag": tag})


def deny(tag: str):
    return make_req("fast-eq.test", headers={"x-org": "evil", "x-tag": tag})


# ---------------------------------------------------------------------------
# a raw client: frames in, frames out, the server's constant header blocks
# read back
# ---------------------------------------------------------------------------

def read_block(block: bytes) -> dict:
    """The fields of a header block of this server's: indexed static
    entries, literals without indexing, size updates (it never indexes)."""
    static = {8: (":status", "200"), 31: ("content-type", "")}
    out, i = {}, 0
    while i < len(block):
        b = block[i]
        if b & 0x80:
            name, value = static[b & 0x7f]
            out[name] = value
            i += 1
        elif b & 0xe0 == 0x20:
            out["size_update"] = b & 0x1f
            i += 1
        else:
            assert b & 0xf0 == 0, f"indexed representation {b:#x}"
            ni = b & 0x0f
            if ni == 15:
                ni += block[i + 1]
                i += 1
            i += 1
            if ni:
                name = static[ni][0]
            else:
                n = block[i]
                name = block[i + 1:i + 1 + n].decode()
                i += 1 + n
            n = block[i]
            out[name] = block[i + 1:i + 1 + n].decode()
            i += 1 + n
    return out


class Client:
    def __init__(self, port: int, settings_frame: bytes | None = None):
        self.s = socket.create_connection(("127.0.0.1", port), timeout=10)
        self.buf = b""
        self.frames: list = []
        self.s.sendall(PREFACE + (settings() if settings_frame is None
                                  else settings_frame))

    def send(self, data: bytes):
        self.s.sendall(data)

    def close(self):
        self.s.close()

    def _pull(self, timeout: float) -> bool:
        self.s.settimeout(timeout)
        try:
            chunk = self.s.recv(1 << 16)
        except socket.timeout:
            return False
        except ConnectionResetError:  # the server closed with our bytes unread
            raise EOFError from None
        if not chunk:
            raise EOFError
        self.buf += chunk
        while len(self.buf) >= 9:
            n = int.from_bytes(self.buf[:3], "big")
            if len(self.buf) < 9 + n:
                break
            ftype, flags = self.buf[3], self.buf[4]
            sid = struct.unpack(">I", self.buf[5:9])[0] & 0x7fffffff
            self.frames.append((ftype, flags, sid, self.buf[9:9 + n]))
            self.buf = self.buf[9 + n:]
        return True

    def wait(self, pred, timeout: float = 10.0):
        """The first frame, old or new, for which pred holds."""
        deadline = time.monotonic() + timeout
        while True:
            for f in self.frames:
                if pred(*f):
                    return f
            left = deadline - time.monotonic()
            if left <= 0:
                raise AssertionError(f"no such frame in {self.frames}")
            self._pull(left)

    def quiet(self, seconds: float):
        """Read whatever comes for `seconds`."""
        deadline = time.monotonic() + seconds
        while (left := deadline - time.monotonic()) > 0:
            self._pull(left)

    def stream(self, sid: int):
        """The stream's frames, but the window updates for what it sent."""
        return [f for f in self.frames if f[2] == sid and f[0] != WINDOW_UPDATE]

    def answer(self, sid: int, timeout: float = 10.0):
        """The stream's answer: (grpc-status, CheckResponse or None)."""
        self.wait(lambda t, fl, s, p: s == sid and (
            (t in (HEADERS, DATA) and fl & END_STREAM) or t == RST_STREAM), timeout)
        frames = self.stream(sid)
        assert frames[0][0] == HEADERS, frames
        first = read_block(frames[0][3])
        assert first[":status"] == "200"
        assert first["content-type"] == "application/grpc"
        if frames[0][1] & END_STREAM:  # trailers-only
            return int(first["grpc-status"]), None
        data = b"".join(p for t, _, _, p in frames if t == DATA)
        assert data[0] == 0 and struct.unpack(">I", data[1:5])[0] == len(data) - 5
        last = frames[-1]
        assert last[0] == HEADERS and last[1] & END_STREAM
        return int(read_block(last[3])["grpc-status"]), pb.CheckResponse.FromString(
            data[5:])

    def goaway(self, timeout: float = 10.0):
        """The GOAWAY's (last stream, error code), and the socket closed."""
        f = self.wait(lambda t, *_: t == GOAWAY, timeout)
        last, code = struct.unpack(">II", f[3][:8])
        with pytest.raises(EOFError):
            self.quiet(timeout)
        return last & 0x7fffffff, code


@pytest.fixture(scope="module")
def frontend():
    engine = build_engine()
    fe = NativeFrontend(engine, port=0, max_batch=16, window_us=500,
                        lane_select=False)
    port = fe.start()
    assert fe.wait_warm(300.0)
    try:
        yield engine, fe, port
    finally:
        fe.stop()


def test_our_huffman_table_is_rfc_7541s():
    # RFC 7541 C.4.1 and C.4.3
    assert huffman(b"www.example.com").hex() == "f1e3c2e5f23a6ba0ab90f4ff"
    assert huffman(b"custom-value").hex() == "25a849e95bb8e8b4bf"


# ---------------------------------------------------------------------------
# raw frames: one case a part of the protocol
# ---------------------------------------------------------------------------

def case_preface_and_settings(fe, port):
    c = Client(port, settings(max_frame_size=1 << 20, header_table_size=0))
    f = c.wait(lambda t, fl, s, p: t == SETTINGS and not fl & ACK)
    entries = dict(struct.unpack(">HI", f[3][i:i + 6]) for i in range(0, len(f[3]), 6))
    assert entries == {3: 10000, 4: 1 << 20}
    wu = c.wait(lambda t, fl, s, p: t == WINDOW_UPDATE and s == 0)
    assert struct.unpack(">I", wu[3])[0] == (1 << 30) - 65535
    c.wait(lambda t, fl, s, p: t == SETTINGS and fl & ACK and not p)
    c.send(frame(SETTINGS, ACK, 0) + request(1, allow("settings")))
    assert c.answer(1)[0] == 0
    # the peer shrank its table: the next block of ours opens with an update to 0
    assert read_block(c.stream(1)[0][3])["size_update"] == 0
    c.send(request(3, allow("settings-2")))
    assert c.answer(3)[0] == 0
    assert "size_update" not in read_block(c.stream(3)[0][3])
    return c


def case_hpack_huffman_and_dynamic_table(fe, port):
    c = Client(port)
    # every string Huffman-coded, four entries inserted: te (62),
    # user-agent (63), content-type (64), :path (65)
    first = (indexed(3) + indexed(6)
             + literal(b":path", CHECK, "with", huff=True)
             + literal(1, b"framer.test", "without", huff=True)
             + literal(31, b"application/grpc", "with", huff=True)
             + literal(58, b"grpc-python/conformance", "with", huff=True)
             + literal(b"te", b"trailers", "with", huff=True)
             + literal(b"x-never", b"secret", "never", huff=True))
    c.send(request(1, allow("hp-1"), first))
    status, resp = c.answer(1)
    assert (status, resp.status.code) == (0, 0)
    # the next block opens with a size update and names every field by index
    second = size_update(4096) + indexed(3) + indexed(6) + indexed(65) + indexed(64) \
        + indexed(63) + indexed(62)
    c.send(request(3, deny("hp-2"), second))
    status, resp = c.answer(3)
    assert (status, resp.status.code) == (0, 7)
    # a table of 100 bytes keeps one of them; :path inserted anew, by name
    # index, evicts the rest
    third = (size_update(0) + size_update(100) + indexed(3)
             + literal(4, CHECK, "with", huff=True))
    c.send(request(5, allow("hp-3"), third))
    assert c.answer(5)[1].status.code == 0
    c.send(request(7, allow("hp-4"), indexed(3) + indexed(62)))
    assert c.answer(7)[1].status.code == 0
    # a second insert evicts that :path; the index then names the new one,
    # the health service's
    c.send(frame(HEADERS, END_HEADERS | END_STREAM, 9,
                 literal(4, HEALTH, "with") + indexed(62)))
    c.wait(lambda t, fl, s, p: s == 9 and t == HEADERS and fl & END_STREAM)
    data = b"".join(p for t, _, s, p in c.frames if s == 9 and t == DATA)
    assert protos.health_pb2.HealthCheckResponse.FromString(data[5:]).status == \
        protos.health_pb2.HealthCheckResponse.SERVING
    return c


def case_continuation(fe, port):
    c = Client(port)
    block = check_block() + literal(b"x-long", b"v" * 300, "without", huff=True)
    cut1, cut2 = 5, 60  # mid-representation both times
    c.send(frame(HEADERS, 0, 1, block[:cut1])
           + frame(CONTINUATION, 0, 1, block[cut1:cut2])
           + frame(CONTINUATION, END_HEADERS, 1, block[cut2:])
           + frame(DATA, END_STREAM, 1, grpc_msg(allow("cont"))))
    assert c.answer(1)[1].status.code == 0
    return c


def case_padded_and_priority(fe, port):
    c = Client(port)
    block = check_block()
    headers = bytes([7]) + struct.pack(">IB", 0, 15) + block + b"\x00" * 7
    msg = grpc_msg(deny("padded"))
    c.send(frame(HEADERS, END_HEADERS | PADDED | PRIO, 1, headers)
           + frame(PRIORITY, 0, 1, struct.pack(">IB", 0, 200))
           + frame(DATA, END_STREAM | PADDED, 1, bytes([33]) + msg + b"\x00" * 33))
    assert c.answer(1)[1].status.code == 7
    return c


def inplace(fe):
    return fe._mod.fe_loop_clock()["rows"]["msg_inplace"]["count"]


def case_message_over_data_frames(fe, port):
    c, before = Client(port), inplace(fe)
    msg = grpc_msg(allow("split"))
    c.send(frame(HEADERS, END_HEADERS, 1, check_block())
           + frame(DATA, 0, 1, msg[:3]) + frame(DATA, 0, 1, msg[3:40])
           + frame(DATA, 0, 1, b"") + frame(DATA, END_STREAM, 1, msg[40:]))
    assert c.answer(1)[1].status.code == 0
    assert inplace(fe) == before  # gathered in the stream
    return c


def case_message_across_a_recv_edge(fe, port):
    c, before = Client(port), inplace(fe)
    req = make_req("fast-eq.test", headers={
        "x-org": "acme", **{f"x-edge-{k}": "e" * 40 for k in range(24)}})
    for sid, back in ((1, 700), (3, 1)):  # mid-DATA, then its last byte
        wire = request(sid, req)
        assert 1300 <= len(wire) <= 1600
        c.send(wire[:-back])
        time.sleep(0.15)  # the server walks what it has: a partial frame
        c.send(wire[-back:])
        assert c.answer(sid)[1].status.code == 0
    # the frame's head carried to the buffer's front, its message read there
    assert inplace(fe) == before + 2
    return c


def case_message_past_64_kb(fe, port):
    c = Client(port)
    req = make_req("fast-eq.test", headers={"x-org": "acme", "x-pad": "p" * 70000})
    msg = grpc_msg(req)
    assert len(msg) > 65536
    out = frame(HEADERS, END_HEADERS, 1, check_block())
    for at in range(0, len(msg), 16384):
        last = at + 16384 >= len(msg)
        out += frame(DATA, END_STREAM if last else 0, 1, msg[at:at + 16384])
    c.send(out)
    assert c.answer(1)[1].status.code == 0
    return c


def case_message_past_16_mib(fe, port):
    c = Client(port)
    chunk = b"m" * 16384
    out = frame(HEADERS, END_HEADERS, 1, check_block())
    out += frame(DATA, 0, 1, b"\x00" + struct.pack(">I", 1030 * 16384) + chunk[5:])
    out += frame(DATA, 0, 1, chunk) * 1028 + frame(DATA, END_STREAM, 1, chunk)
    c.send(out)
    assert c.answer(1) == (RESOURCE_EXHAUSTED, None)  # not gathered past 16 MiB
    # trailers for the stream, closed here now, are dropped
    c.send(frame(HEADERS, END_HEADERS | END_STREAM, 1, literal(b"x-t", b"1")))
    return c


def case_a_peer_window_of_zero_holds_the_answer(fe, port):
    c = Client(port, settings(initial_window_size=0))
    c.send(request(1, deny("window")))
    c.wait(lambda t, fl, s, p: t == HEADERS and s == 1)
    c.quiet(0.5)
    assert [f[0] for f in c.stream(1)] == [HEADERS]  # the DATA is held
    c.send(frame(WINDOW_UPDATE, 0, 1, struct.pack(">I", 10)))
    c.wait(lambda t, fl, s, p: t == DATA and s == 1)
    c.quiet(0.2)
    assert [(f[0], len(f[3])) for f in c.stream(1)] == [(HEADERS, 20), (DATA, 10)]
    c.send(frame(WINDOW_UPDATE, 0, 1, struct.pack(">I", 4096)))
    assert c.answer(1)[1].status.code == 7
    c.send(settings(initial_window_size=65535))  # for the streams after
    return c


def case_reset_stream_with_its_cut_in_flight(fe, port):
    stats = fe.stats()
    c = Client(port)
    c.send(request(1, allow("reset-me"))
           + frame(RST_STREAM, 0, 1, struct.pack(">I", 8)))
    c.send(request(3, allow("after-reset")))
    assert c.answer(3)[1].status.code == 0
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        moved = {k: fe.stats()[k] - stats[k] for k in ("fast", "allowed", "denied")}
        if moved["allowed"] + moved["denied"] >= 2:
            break
        time.sleep(0.02)
    assert moved == {"fast": 2, "allowed": 2, "denied": 0}  # both rows' cuts completed
    c.quiet(0.3)
    assert c.stream(1) == []  # and the reset one's answer was dropped
    return c


def case_ping(fe, port):
    c = Client(port)
    c.send(frame(PING, 0, 0, b"8 bytes!"))
    f = c.wait(lambda t, fl, s, p: t == PING)
    assert (f[1], f[2], f[3]) == (ACK, 0, b"8 bytes!")
    return c


def case_unknown_frame_type(fe, port):
    c = Client(port)
    c.send(frame(0xfa, 0xff, 1, b"ignore me") + frame(0x0b, 0, 0, b"")
           + request(1, allow("unknown")))
    assert c.answer(1)[1].status.code == 0
    return c


def case_health(fe, port):
    c = Client(port)
    body = protos.health_pb2.HealthCheckRequest().SerializeToString()
    c.send(frame(HEADERS, END_HEADERS, 1, check_block(HEALTH))
           + frame(DATA, END_STREAM, 1, b"\x00" + struct.pack(">I", len(body)) + body))
    c.wait(lambda t, fl, s, p: s == 1 and t == HEADERS and fl & END_STREAM)
    data = b"".join(p for t, _, s, p in c.frames if s == 1 and t == DATA)
    resp = protos.health_pb2.HealthCheckResponse.FromString(data[5:])
    assert resp.status == protos.health_pb2.HealthCheckResponse.SERVING
    return c


def case_unknown_path(fe, port):
    c = Client(port)
    c.send(request(1, allow("nope"), check_block(b"/envoy.service.auth.v3.Authorization/Nope")))
    assert c.answer(1) == (12, None)  # UNIMPLEMENTED, trailers only
    return c


def case_compressed_check(fe, port):
    c = Client(port)
    body = allow("gzip").SerializeToString()
    c.send(frame(HEADERS, END_HEADERS, 1, check_block() + literal(b"grpc-encoding", b"gzip"))
           + frame(DATA, END_STREAM, 1, b"\x01" + struct.pack(">I", len(body)) + body))
    assert c.answer(1) == (12, None)
    # the compressed flag without the header
    c.send(frame(HEADERS, END_HEADERS, 3, check_block())
           + frame(DATA, END_STREAM, 3, b"\x01" + struct.pack(">I", len(body)) + body))
    assert c.answer(3) == (12, None)
    return c


RAW_CASES = {name[len("case_"):]: fn for name, fn in globals().items()
             if name.startswith("case_")}


@pytest.mark.parametrize("case", sorted(RAW_CASES))
def test_raw_frames(frontend, case):
    _, fe, port = frontend
    c = RAW_CASES[case](fe, port)
    try:
        # the connection serves on after the case
        c.frames.clear()
        c.send(request(101, allow(f"after-{case}")))
        assert c.answer(101)[1].status.code == 0
    finally:
        c.close()


# ---------------------------------------------------------------------------
# what the framer refuses: a connection error, GOAWAY, and the socket closed
# ---------------------------------------------------------------------------

# each after stream 1's request and answer: the bytes, the error code, and
# the last stream the GOAWAY names
MALFORMED = {
    "data_on_stream_0": (frame(DATA, 0, 0, b"x"), PROTOCOL_ERROR, 1),
    "even_stream": (frame(HEADERS, END_HEADERS, 2, check_block()), PROTOCOL_ERROR, 1),
    "continuation_out_of_place": (frame(CONTINUATION, END_HEADERS, 3, b"\x83"),
                                  PROTOCOL_ERROR, 1),
    "frame_in_a_header_block": (frame(HEADERS, 0, 3, b"\x83") + frame(PING, 0, 0, b"x" * 8),
                                PROTOCOL_ERROR, 1),
    "ping_of_4_bytes": (frame(PING, 0, 0, b"abcd"), FRAME_SIZE_ERROR, 1),
    "settings_of_5_bytes": (frame(SETTINGS, 0, 0, b"\x00\x04\x00\x00\x00"),
                            FRAME_SIZE_ERROR, 1),
    "frame_past_16_kb": (frame(DATA, 0, 3, b"x" * 16385), FRAME_SIZE_ERROR, 1),
    "window_update_of_0": (frame(WINDOW_UPDATE, 0, 0, b"\x00" * 4), PROTOCOL_ERROR, 1),
    "padding_past_the_frame": (frame(HEADERS, END_HEADERS, 3, check_block())
                               + frame(DATA, PADDED | END_STREAM, 3, b"\x09abc"),
                               PROTOCOL_ERROR, 3),
    "index_past_the_table": (frame(HEADERS, END_HEADERS, 3, indexed(70)),
                             COMPRESSION_ERROR, 1),
    # "/ab" is 17 bits: seven bits of padding, zeros here, where ones belong
    "huffman_padded_with_zeros": (frame(HEADERS, END_HEADERS, 3, bytes([0x04])
                                        + hp_int(len(huffman(b"/ab", 0)), 7, 0x80)
                                        + huffman(b"/ab", 0)), COMPRESSION_ERROR, 1),
    "table_past_4096": (frame(HEADERS, END_HEADERS, 3, size_update(4097) + indexed(3)),
                        COMPRESSION_ERROR, 1),
    "size_update_mid_block": (frame(HEADERS, END_HEADERS, 3, indexed(3) + size_update(0)),
                              COMPRESSION_ERROR, 1),
    # stream 5 opened and left open, then a new stream below it
    "new_stream_below_the_last": (frame(HEADERS, END_HEADERS, 5, check_block())
                                  + frame(HEADERS, END_HEADERS, 3, check_block()),
                                  PROTOCOL_ERROR, 5),
}


@pytest.mark.parametrize("case", sorted(MALFORMED) + ["not_settings_first"])
def test_malformed_frames_are_answered_with_goaway(frontend, case):
    _, fe, port = frontend
    if case == "not_settings_first":
        c = Client(port, frame(PING, 0, 0, b"x" * 8))
        want = PROTOCOL_ERROR
    else:
        c = Client(port)
        c.send(request(1, allow("before")))
        assert c.answer(1)[1].status.code == 0
        wire, want, want_last = MALFORMED[case]
        c.send(wire)
    try:
        last, code = c.goaway()
        assert code == want
        assert last == (0 if case == "not_settings_first" else want_last)
    finally:
        c.close()
    # the server answers the next client
    c = Client(port)
    try:
        c.send(request(1, allow("after")))
        assert c.answer(1)[1].status.code == 0
    finally:
        c.close()


# ---------------------------------------------------------------------------
# a peer that sends and never reads: what it makes the server hold is bounded
# ---------------------------------------------------------------------------

FLOOD_LIMIT = 64 << 20  # bytes sent: far past what the bounds let through


def _flood(port, chunks):
    """Send `chunks()` without reading until the server closes the
    connection or has stopped reading it for a second: ("closed" | "stalled",
    bytes sent, the socket, the unsent rest of the last chunk)."""
    s = socket.create_connection(("127.0.0.1", port))
    s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 256 << 10)
    s.setblocking(False)
    sent, pending, stalled_at = 0, b"", None
    try:
        while sent < FLOOD_LIMIT:
            if not pending:
                pending = next(chunks)
            try:
                n = s.send(pending)
            except BlockingIOError:
                stalled_at = stalled_at or time.monotonic()
                if time.monotonic() - stalled_at > 1.0:
                    return "stalled", sent, s, pending
                time.sleep(0.005)
                continue
            sent, pending, stalled_at = sent + n, pending[n:], None
    except (ConnectionResetError, BrokenPipeError):
        s.close()
        return "closed", sent, None, b""
    return "unbounded", sent, s, pending


def _pings():
    yield PREFACE + settings()
    chunk = frame(PING, 0, 0, b"pingpong") * 4096
    while True:
        yield chunk


CHECKS_OPEN = (PREFACE + settings(initial_window_size=(1 << 31) - 1)
               + frame(WINDOW_UPDATE, 0, 0, struct.pack(">I", (1 << 31) - 1 - 65535)))


def _check_wire(sid: int) -> bytes:
    msg = grpc_msg(make_req("nobody.test"))  # not found: answered at once
    return frame(HEADERS, END_HEADERS, sid, check_block()) + frame(DATA, END_STREAM, sid, msg)


def _checks():
    # the windows opened, as Envoy does: no answer is held by flow control
    yield CHECKS_OPEN
    sid = 1
    while True:
        yield b"".join(_check_wire(sid + 2 * k) for k in range(1000))
        sid += 2000


def _kernel_send_buffer_max() -> int:
    with open("/proc/sys/net/ipv4/tcp_wmem") as f:
        return int(f.read().split()[2])


@pytest.mark.parametrize("flood", ["pings", "checks"])
def test_a_peer_that_does_not_read_is_held_to_a_bound(frontend, flood):
    _, fe, port = frontend
    answered0 = fe.stats()["notfound"]
    how, sent, s, pending = _flood(port, _pings() if flood == "pings" else _checks())
    try:
        assert how != "unbounded", f"the server read {sent} bytes and never stopped"
        if flood == "pings":
            # past 1,000 PING ACKs queued while the peer took nothing:
            # GOAWAY ENHANCE_YOUR_CALM, and the socket closed
            assert how == "closed"
        else:
            # the server stopped reading once 1 MiB of answers waited: what
            # it answered is what that and the kernel's buffers, its and
            # ours, hold (an answer is 67 bytes at the least)
            assert how == "stalled"
            answered = fe.stats()["notfound"] - answered0
            held = _kernel_send_buffer_max() + s.getsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF)
            assert 0 < answered * 67 <= held + (2 << 20)
            # the peer reads again: the server reads on, and answers them all
            streams = (sent + len(pending) - len(CHECKS_OPEN)) // len(_check_wire(1))
            stop = threading.Event()

            def drain():
                while not stop.is_set():
                    try:
                        s.recv(1 << 20)
                    except BlockingIOError:
                        time.sleep(0.002)

            reader = threading.Thread(target=drain)
            reader.start()
            try:
                while pending:
                    try:
                        pending = pending[s.send(pending):]
                    except BlockingIOError:
                        time.sleep(0.002)
                deadline = time.monotonic() + 30
                while (fe.stats()["notfound"] - answered0 < streams
                       and time.monotonic() < deadline):
                    time.sleep(0.01)
            finally:
                stop.set()
                reader.join()
            assert fe.stats()["notfound"] - answered0 == streams > answered
    finally:
        if s is not None:
            s.close()
    # the server answers the next client
    c = Client(port)
    try:
        c.send(request(1, allow("after-flood")))
        assert c.answer(1)[1].status.code == 0
    finally:
        c.close()


# ---------------------------------------------------------------------------
# grpcio: a whole HTTP/2 stack, many channels, many Checks in flight
# ---------------------------------------------------------------------------

def test_grpcio_channels_with_checks_in_flight(frontend):
    engine, fe, port = frontend
    holder, t = run_python_server(engine)
    try:
        with grpc.insecure_channel(f"127.0.0.1:{holder['port']}") as ch:
            ref = ch.unary_unary(CHECK.decode(),
                                 request_serializer=pb.CheckRequest.SerializeToString,
                                 response_deserializer=pb.CheckResponse.FromString)
            want = [response_key(ref(r, timeout=10)) for r in REQUESTS]
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=10)
    channels = [grpc.insecure_channel(
        f"127.0.0.1:{port}", options=[("grpc.use_local_subchannel_pool", 1)])
        for _ in range(8)]
    try:
        calls = [ch.unary_unary(CHECK.decode(),
                                request_serializer=pb.CheckRequest.SerializeToString,
                                response_deserializer=pb.CheckResponse.FromString)
                 for ch in channels]
        for rnd in range(2):
            futures = [(k, calls[c].future(REQUESTS[k], timeout=30))
                       for c in range(8) for k in
                       ((rnd * 128 + j) % len(REQUESTS) for j in range(128))]
            got = [(k, response_key(f.result())) for k, f in futures]
            assert all(key == want[k] for k, key in got), [
                (k, key, want[k]) for k, key in got if key != want[k]][:5]
    finally:
        for ch in channels:
            ch.close()
    assert fe.stats()["fast"] > 0


def test_the_server_loads_no_libnghttp2(frontend):
    _, _, port = frontend
    c = Client(port)
    try:
        c.send(request(1, allow("maps")))
        assert c.answer(1)[1].status.code == 0
    finally:
        c.close()
    with open("/proc/self/maps") as f:
        assert "libnghttp2" not in f.read()
