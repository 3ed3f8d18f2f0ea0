"""Host-level transport for the distributed actor plane.

Capability parity with reference handyrl/connection.py: length-prefixed
framing (connection.py:20-69), ``send_recv`` RPC (14-17), socket helpers
(72-114), and the ``QueueCommunicator`` async hub (176-224).  Differences:

* Frames carry the pickle-free codec (runtime/codec.py), not pickle.
* This layer only moves *actor-plane* traffic (job args, episodes, eval
  results, param blobs).  The gradient/param plane inside the learner is
  XLA collectives over ICI/DCN (parallel/train_step.py) and never touches
  these sockets — the two planes the reference conflates are split by
  design (SURVEY.md §2.5).
* Fault tolerance (docs/fault_tolerance.md): frame send/recv take
  optional deadlines (a WAN blackhole must surface as TimeoutError, not
  an eternal block), and the hub gives each peer its OWN bounded send
  queue + sender thread, so one stalled peer's TCP backpressure can never
  wedge delivery to every other peer.
"""

from __future__ import annotations

import io
import queue
import select
import socket
import struct
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

from . import codec

_HEADER = struct.Struct("!I")

_UNSET = object()  # "use the connection default" sentinel for timeouts


def _wait_io(sock, for_write: bool, deadline: float) -> None:
    """Block until the socket (or raw fd) is ready for the given direction
    or the deadline passes (raises socket.timeout).

    Readiness-polling instead of ``settimeout``: the socket's timeout is
    SHARED state, and one connection is legitimately used by an
    independent sender and receiver thread at once (QueueCommunicator) —
    a sender calling settimeout(None) between the receiver's
    settimeout(30) and its recv syscall would silently strip the
    receiver's dead-peer deadline.  poll/select mutate nothing.  Also the
    readiness-wait primitive for non-socket fds (the shm pipeline's ready
    pipe) — accept an int fd directly.
    """
    remaining = deadline - time.monotonic()
    if remaining > 0:
        try:
            fd = sock if isinstance(sock, int) else sock.fileno()
            if fd < 0:
                raise OSError("socket closed")
            if hasattr(select, "poll"):  # no FD_SETSIZE cap (select does)
                poller = select.poll()
                poller.register(fd, select.POLLOUT if for_write else select.POLLIN)
                if poller.poll(remaining * 1000.0):
                    return
            else:  # pragma: no cover - non-poll platforms
                rw = ([], [sock]) if for_write else ([sock], [])
                if any(select.select(*rw, [], remaining)[:2]):
                    return
        except ValueError:
            raise OSError("socket closed")
    raise socket.timeout(
        f"{'send' if for_write else 'recv'} deadline exceeded"
    )


class FramedConnection:
    """u32-length-prefixed codec frames over a stream socket.

    ``timeout`` (constructor default, overridable per call) bounds the
    SILENCE on each send/recv — how long the transfer may stall without a
    byte of progress, not how long the whole frame may take (a large
    params blob on a slow link is alive as long as bytes flow).  On
    expiry the call raises ``TimeoutError`` (socket.timeout) and the
    stream must be considered dead — a deadline can fire mid-frame,
    leaving the framing desynchronized, so the only safe recovery is to
    close and re-establish the connection.  The underlying socket stays in
    blocking mode; deadlines are enforced by readiness polling, so the
    sender's and receiver's deadlines never interfere (see ``_wait_io``).
    """

    def __init__(self, conn: socket.socket, timeout: Optional[float] = None):
        self.conn = conn
        self.default_timeout = timeout
        self._send_lock = threading.Lock()
        self._recv_lock = threading.Lock()
        # frame-payload byte tallies (headers included), updated under the
        # respective direction's lock: tests/test_fleet.py reads these to
        # measure wire bytes/request — session routing's whole claim
        self.bytes_sent = 0
        self.bytes_received = 0

    def fileno(self) -> int:
        return self.conn.fileno()

    def close(self) -> None:
        try:
            # shutdown, not just close: close() of the fd does NOT wake a
            # thread blocked inside a send/recv syscall on this socket
            # (it would stay wedged forever, stranding e.g. a hub sender
            # thread mid-sendall); shutdown() forces those syscalls to
            # return so teardown actually tears down
            self.conn.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.conn.close()
        except OSError:
            pass

    def _gap(self, timeout) -> Optional[float]:
        t = self.default_timeout if timeout is _UNSET else timeout
        return None if t is None else float(t)

    def _recv_exact(
        self, n: int, gap: Optional[float], hard_deadline: Optional[float] = None
    ) -> bytes:
        buf = io.BytesIO()
        while buf.tell() < n:
            if gap is not None or hard_deadline is not None:
                # the gap deadline restarts on every chunk: it bounds
                # SILENCE, not total frame time — a multi-hundred-MB params
                # blob trickling over a slow WAN is alive by construction
                # (progress is the liveness proof) and must never be cut
                # off mid-transfer by a whole-frame budget.  hard_deadline
                # is the opposite mode, for tiny control frames (entry
                # handshake): an ABSOLUTE budget a byte-trickler cannot
                # keep alive by dribbling one byte per gap
                if gap is None:
                    deadline = hard_deadline
                elif hard_deadline is None:
                    deadline = time.monotonic() + gap
                else:
                    deadline = min(time.monotonic() + gap, hard_deadline)
                _wait_io(self.conn, False, deadline)
            chunk = self.conn.recv(n - buf.tell())
            if not chunk:
                raise ConnectionResetError("connection closed mid-frame")
            buf.write(chunk)
        return buf.getvalue()

    def recv(self, timeout=_UNSET, hard: bool = False) -> Any:
        """``hard`` turns ``timeout`` into an absolute whole-frame budget
        instead of a stall bound — see ``_recv_exact``."""
        with self._recv_lock:
            gap = self._gap(timeout)
            hard_deadline = None
            if hard and gap is not None:
                hard_deadline, gap = time.monotonic() + gap, None
            (length,) = _HEADER.unpack(self._recv_exact(4, gap, hard_deadline))
            payload = self._recv_exact(length, gap, hard_deadline) if length else b""
            self.bytes_received += 4 + length
        return codec.loads(payload)

    def send(self, obj: Any, timeout=_UNSET, hard: bool = False) -> None:
        payload = codec.dumps(obj)
        data = _HEADER.pack(len(payload)) + payload
        with self._send_lock:
            self._send_bytes(data, self._gap(timeout), hard)

    def try_send(self, obj: Any, timeout=_UNSET) -> bool:
        """``send`` iff no other frame is in flight on this connection;
        returns False (without blocking) otherwise.

        The liveness-ping use case: a frame already being sent proves the
        link alive better than a queued ping would, and a ping thread
        blocking behind a multi-minute trickling upload would starve its
        OTHER duties (pinging the sibling connections)."""
        payload = codec.dumps(obj)
        if not self._send_lock.acquire(blocking=False):
            return False
        try:
            self._send_bytes(_HEADER.pack(len(payload)) + payload, self._gap(timeout))
        finally:
            self._send_lock.release()
        return True

    def _send_bytes(self, data: bytes, gap: Optional[float], hard: bool = False) -> None:
        """Write one frame; caller holds the send lock."""
        self.bytes_sent += len(data)
        if gap is None:
            self.conn.sendall(data)
            return
        hard_deadline = time.monotonic() + gap if hard else None
        view = memoryview(data)
        while view:
            # writable after poll => send() accepts >= 1 byte without
            # blocking (send_lock serializes writers on this socket);
            # like recv, the gap bounds stall time, not frame time —
            # unless ``hard``, the absolute-budget mode for control frames
            # whose peer could drip-READ to keep the gap alive
            _wait_io(
                self.conn, True,
                hard_deadline if hard else time.monotonic() + gap,
            )
            view = view[self.conn.send(view):]


def send_recv(conn: FramedConnection, sdata: Any, timeout=_UNSET) -> Any:
    conn.send(sdata, timeout=timeout)
    return conn.recv(timeout=timeout)


def open_socket_connection(port: int, reuse: bool = True) -> socket.socket:
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1 if reuse else 0)
    sock.bind(("", int(port)))
    return sock


def accept_socket_connections(
    port: Optional[int] = None,
    timeout: Optional[float] = None,
    maxsize: Optional[int] = None,
    sock: Optional[socket.socket] = None,
) -> Iterator[Optional[FramedConnection]]:
    """Yield accepted FramedConnections (None on timeout) until closed.

    ``maxsize`` bounds the total accept count when given; the default is
    unbounded — long-lived servers (elastic worker fleets, battle servers)
    must never silently stop accepting.
    """
    if sock is None:
        sock = open_socket_connection(port)
    sock.listen(1024)
    sock.settimeout(timeout)
    count = 0
    while maxsize is None or count < maxsize:
        try:
            conn, _ = sock.accept()
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            conn.settimeout(None)  # accept() propagates the listener timeout
            yield FramedConnection(conn)
            count += 1
        except socket.timeout:
            yield None
        except OSError:
            return


def connect_socket_connection(
    host: str, port: int, timeout: float = 32.0, retry_seconds: float = 0.0
) -> FramedConnection:
    """Connect, optionally retrying for ``retry_seconds`` (peer still booting)."""
    import time

    deadline = time.monotonic() + retry_seconds
    while True:
        try:
            sock = socket.create_connection((host, int(port)), timeout=timeout)
            break
        except OSError:
            if time.monotonic() >= deadline:
                raise
            time.sleep(0.5)
    sock.settimeout(None)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return FramedConnection(sock)


class QueueCommunicator:
    """Async fan-in hub over many connections (connection.py:176-224).

    A daemon receiver thread per connection funnels frames into
    ``input_queue``; a daemon SENDER thread per connection drains that
    peer's own bounded send queue.  Per-peer send queues are the fault
    boundary: a peer that stops reading fills its TCP window, then its
    queue, and is disconnected — every other peer keeps flowing (the
    previous single shared send loop let one wedged ``sendall`` starve
    all peers).  ``recv_timeout`` bounds each peer's frame gap; a peer
    silent for longer (no traffic, no heartbeat) is presumed dead and
    dropped, so half-open TCP connections cannot pin receiver threads or
    the connection count forever.
    """

    def __init__(
        self,
        conns: Optional[List[FramedConnection]] = None,
        recv_timeout: Optional[float] = None,
        send_queue_size: int = 64,
    ):
        self.input_queue: "queue.Queue[Tuple[FramedConnection, Any]]" = queue.Queue(maxsize=256)
        self.conns: Dict[FramedConnection, "queue.Queue"] = {}
        self.recv_timeout = recv_timeout
        self.send_queue_size = send_queue_size
        self._lock = threading.Lock()
        self.shutdown_flag = False
        for conn in conns or []:
            self.add_connection(conn)

    def connection_count(self) -> int:
        with self._lock:
            return len(self.conns)

    def connections(self) -> List[FramedConnection]:
        with self._lock:
            return list(self.conns)

    def recv(self, timeout: Optional[float] = None) -> Tuple[FramedConnection, Any]:
        return self.input_queue.get(timeout=timeout)

    def send(self, conn: FramedConnection, send_data: Any, droppable: bool = False) -> None:
        with self._lock:
            send_q = self.conns.get(conn)
        if send_q is None:
            return  # peer already gone; its jobs were reclaimed on disconnect
        try:
            send_q.put_nowait(send_data)
        except queue.Full:
            if droppable:
                # e.g. a liveness ping queued behind a long in-flight blob
                # transfer: the peer is demonstrably alive (bytes flowing),
                # so drop the PING, not the peer — disconnecting here would
                # re-impose the whole-frame time budget the frame layer
                # deliberately avoids
                return
            # TCP window AND the queue are full: the peer stopped reading
            # long ago — tear it down rather than buffer without bound
            print("peer send queue overflow, dropping connection")
            self.disconnect(conn)

    def shutdown(self) -> None:
        self.shutdown_flag = True
        for conn in self.connections():
            self.disconnect(conn)

    def add_connection(self, conn: FramedConnection) -> None:
        send_q: "queue.Queue" = queue.Queue(maxsize=self.send_queue_size)
        with self._lock:
            self.conns[conn] = send_q
        # one receiver thread per connection: blocking recv() needs no
        # select() dance and each frame lands on input_queue in order
        threading.Thread(target=self._recv_loop, args=(conn,), daemon=True).start()
        threading.Thread(target=self._send_loop, args=(conn, send_q), daemon=True).start()

    def disconnect(self, conn: FramedConnection) -> None:
        with self._lock:
            send_q = self.conns.pop(conn, None)
        conn.close()
        if send_q is not None:
            try:
                send_q.put_nowait(_UNSET)  # wake the sender thread to exit
            except queue.Full:
                pass  # sender will notice the closed socket on its next send
            self.on_disconnect(conn)

    def on_disconnect(self, conn: FramedConnection) -> None:
        """Hook: called once per peer actually removed (subclasses reclaim
        the peer's in-flight jobs here).  Runs on whichever thread noticed
        the failure; keep it non-blocking."""

    def _recv_loop(self, conn: FramedConnection) -> None:
        while not self.shutdown_flag:
            try:
                data = conn.recv(timeout=self.recv_timeout)
            except socket.timeout:
                # silent past the deadline: presumed dead (live peers
                # heartbeat well inside recv_timeout)
                self.disconnect(conn)
                return
            except (ConnectionResetError, BrokenPipeError, EOFError, OSError, codec.CodecError):
                self.disconnect(conn)
                return
            with self._lock:
                if conn not in self.conns:
                    return
            self.input_queue.put((conn, data))

    def _send_loop(self, conn: FramedConnection, send_q: "queue.Queue") -> None:
        while True:
            data = send_q.get()
            if data is _UNSET:
                return  # disconnected while idle
            with self._lock:
                if conn not in self.conns:
                    return
            try:
                conn.send(data)
            except (socket.timeout, ConnectionResetError, BrokenPipeError, OSError):
                self.disconnect(conn)
                return
            except Exception as exc:
                # e.g. CodecError on an unencodable reply: drop that peer —
                # only ITS sender thread dies, every other peer keeps flowing
                print("send failed, dropping connection:", exc)
                self.disconnect(conn)
                return
