"""Buffered reads of MySQL packets, for both ends of a connection (ref:
server/packetio.go, whose packetIO reads through a bufio.Reader).

One `recv` takes whatever burst of bytes has arrived, up to BUFFER_BYTES,
and packets are cut from that buffer: the socket is touched again only
when the buffer runs short. A socket call is where a thread gives the
interpreter lock away and queues for it behind every other connection's
thread, so a seven-packet result set read with fourteen `recv`s costs
fourteen such queues and read with one costs one."""

from __future__ import annotations

from typing import Tuple

#: what one `recv` asks for, and the size at which the server's write
#: buffer goes out (tidb_tpu/server `_Conn`): one constant for both
#: directions
BUFFER_BYTES = 64 * 1024


class PacketReader:
    """Packets off one socket. `ahead` False reads exactly the bytes of
    the packet asked for and leaves the rest in the kernel: what a reader
    must do while the bytes that follow may belong to another protocol
    (the TLS hello after an SSLRequest). `recvs` and `packets` count the
    socket calls made and the packets handed out since whoever reads
    them last set them to 0."""

    __slots__ = ("sock", "ahead", "recvs", "packets", "_buf", "_pos")

    def __init__(self, sock, ahead: bool = True):
        self.sock = sock
        self.ahead = ahead
        self.recvs = 0
        self.packets = 0
        self._buf = b""
        self._pos = 0

    def read_packet(self) -> Tuple[int, bytes]:
        """→ (sequence id, payload) of the next packet; ConnectionError
        when the peer closed."""
        header = self._take(4)
        length = header[0] | (header[1] << 8) | (header[2] << 16)
        self.packets += 1
        return header[3], (self._take(length) if length else b"")

    def _take(self, n: int) -> bytes:
        buf, pos = self._buf, self._pos
        if len(buf) - pos < n:
            parts = [buf[pos:]]
            have = len(parts[0])
            while have < n:
                want = n - have
                part = self.sock.recv(max(want, BUFFER_BYTES)
                                      if self.ahead else want)
                if not part:
                    raise ConnectionError("peer closed the connection")
                self.recvs += 1
                parts.append(part)
                have += len(part)
            buf, pos = b"".join(parts), 0
        end = pos + n
        if end == len(buf):
            # used up: an idle connection holds no spent burst (a bulk
            # INSERT's command is megabytes)
            self._buf, self._pos = b"", 0
        else:
            self._buf, self._pos = buf, end
        return buf[pos:end]
