"""Split actor/learner device planes: cross-mesh param + record flow.

The fused north-star loop is production-bound by construction: one
self-play env-step costs ~100x one trained env-step in device time, so a
single program queue spends >90% of its time in rollout however the duty
cycle is tuned.  The Podracer/
Sebulba answer (Hessel et al. 2021; IMPALA, Espeholt et al. 2018) is to
stop time-slicing: pin self-play to an **actor mesh** and training to a
disjoint **learner mesh** (parallel/mesh.py:split_mesh) so both planes
run at full duty concurrently — made safe by the per-device dispatch
locks (disjoint planes share no lock).  Two flows cross the planes:

* params, learner -> actor: ``PlaneParamCache`` holds a versioned
  replicated copy on the actor mesh, refreshed by a cross-mesh
  ``device_put`` every ``param_refresh_updates`` learner steps; staleness
  is the ``plane_param_lag`` metric (actor params are at most that many
  updates behind — the same staleness the IMPALA off-policy corrections
  in ops/losses.py absorb).
* trajectories, actor -> learner: ``transfer_records`` re-lays a
  streaming rollout's (K, B, ...) record batch out on the learner mesh so
  DeviceReplay (whose rings — and donation-safety contract — live on the
  learner plane) can ingest it.

Both directions count bytes so metrics.jsonl can report the cross-mesh
transfer rate (``plane_xfer_bytes_per_sec``).

**Pod-slice rung 2** (docs/performance.md §Pod-slice topology): the same
two flows generalized across HOSTS.  ``PlaneGateway`` is the learner-side
TCP server (the health plane's framing: newline-delimited JSON headers,
here followed by byte-counted npz payloads) and ``PlaneClient`` the
actor-host side.  Params flow learner -> actor hosts as monotonically
versioned snapshots (an actor polls with the version it has; the gateway
answers bytes only when newer); records flow actor hosts -> learner over
DCN and land in the learner's device rings through the same ingest path
local rollouts use.  Actor hosts stay OUTSIDE jax.distributed by design:
a lost actor host must be a throughput degrade (survivors absorb its game
quota), never a wedged collective — the asymmetry
docs/fault_tolerance.md's matrix pins.
"""

from __future__ import annotations

import io
import json
import socket
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..utils.trace import trace_span


def _tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def _local_view(x):
    """A process-local view of one param leaf, safe to hand to device_put
    or np.asarray.

    Under a multi-process run the learner's params live REPLICATED on the
    global train mesh, which is not fully addressable from any one
    process — and device_put of such an array onto a local mesh has been
    observed (CPU backend) to silently rewrap the sharding metadata
    WITHOUT moving the buffers, handing the actor plane's Execute()
    learner-device buffers (it kills the rollout thread with placement
    errors); np.asarray on one raises outright.  A replicated array's
    value is whole on every addressable shard, so shard 0 IS the value;
    return that single-device array, which copies like any local one."""
    if not isinstance(x, jax.Array) or x.sharding.is_fully_addressable:
        return x
    if not x.sharding.is_fully_replicated:
        raise ValueError(
            "cross-plane publish needs replicated params; got "
            f"sharding {x.sharding} for shape {x.shape}"
        )
    return x.addressable_shards[0].data


class PlaneParamCache:
    """Versioned replicated param copy on the actor mesh.

    The learner thread calls ``publish(params, version)`` between train
    dispatches (the params are the just-returned state's — still valid;
    the copy dispatched here holds its own buffer reference, so the next
    step's donation cannot pull it out from under the transfer).  The
    actor thread reads ``latest()`` each rollout dispatch.  Versions are
    learner step counts and must advance monotonically — pinned by
    tests/test_plane.py.
    """

    def __init__(self, actor_mesh):
        self.mesh = actor_mesh
        self._sharding = NamedSharding(actor_mesh, PartitionSpec())
        self._lock = threading.Lock()
        self._params = None
        self.version = -1
        self.refreshes = 0
        self.bytes_transferred = 0

    def publish(self, params, version: int) -> None:
        """Cross-mesh copy of ``params`` onto the actor mesh (replicated),
        stamped ``version``.  Monotonicity is enforced: the planes'
        staleness accounting is meaningless if versions can rewind."""
        version = int(version)
        with self._lock:
            if version <= self.version:
                raise ValueError(
                    f"param version must advance monotonically: "
                    f"{version} <= {self.version}"
                )
            # the device_put stays under the lock so a concurrent publisher
            # cannot interleave between check and store (the dispatch is
            # async — latest() readers block only for the enqueue)
            fresh = jax.device_put(
                jax.tree.map(self._local_view, params), self._sharding
            )
            self._params = fresh
            self.version = version
            self.refreshes += 1
            self.bytes_transferred += _tree_bytes(fresh)

    _local_view = staticmethod(_local_view)

    def latest(self) -> Tuple[int, Any]:
        """(version, actor-mesh params) of the newest published copy."""
        with self._lock:
            if self._params is None:
                raise RuntimeError("PlaneParamCache.latest() before first publish")
            return self.version, self._params

    def lag(self, learner_steps: int) -> int:
        """How many learner updates behind the actor plane's params are."""
        return max(0, int(learner_steps) - self.version) if self.refreshes else 0


class RecordTransfer:
    """Actor -> learner record re-layout with byte accounting.

    A streaming rollout's (K, B, ...) record batch lives lane-sharded on
    the actor mesh; DeviceReplay's ingest program runs on the learner
    mesh and its jit pins ``in_shardings`` there, so the batch must move
    first.  ``device_put`` to the learner sharding is that move (host
    round-trip on CPU, direct transfer where the runtime supports it);
    the dispatch needs NO plane lock — a copy is not a collective-bearing
    program, so it cannot perturb either plane's program order.
    """

    def __init__(self, learner_mesh):
        self.mesh = learner_mesh
        self._sharding = NamedSharding(learner_mesh, PartitionSpec(None, "dp"))
        self.transfers = 0
        self.bytes_transferred = 0

    def __call__(self, records: Dict[str, Any]) -> Dict[str, Any]:
        moved = jax.device_put(records, self._sharding)
        self.transfers += 1
        self.bytes_transferred += _tree_bytes(moved)
        return moved


class PlaneStats:
    """Shared cumulative counters for the split-plane loop, read (and
    diffed per epoch) by the learner's metrics record.  All writers hold
    the lock; snapshot() returns a plain dict."""

    def __init__(self):
        self._lock = threading.Lock()
        self._c: Dict[str, float] = {
            "actor_dispatches": 0.0,
            "actor_busy_s": 0.0,     # inside rollout dispatch + ingest
            "actor_idle_s": 0.0,     # backpressure sleeps / server waits
            "param_lag_sum": 0.0,    # summed over rollout dispatches
        }

    def bump(self, **kv: float) -> None:
        with self._lock:
            for k, v in kv.items():
                self._c[k] += v

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._c)


# -- rung 2: cross-HOST transports (docs/performance.md §Pod-slice) ----------
#
# Wire protocol, shared by both directions (the health plane's framing
# plus byte-counted payloads):
#
#   header:  one JSON line ending "\n"
#            {"kind": ..., "nbytes": N, ...}
#   payload: exactly N raw bytes (an npz of the tree's leaves keyed by
#            "\x1f"-joined dict paths), present iff nbytes > 0
#
# Every request gets exactly one reply.  A gateway that is shutting down
# answers {"kind": "stop"} — the client exits CLEANLY; a dead socket is
# the loud path (the actor host announces and exits 75: its learner is
# gone, so relaunch-and-reconnect is the only recovery).


def resolve_plane_port(dist_args: Dict[str, Any]) -> int:
    """The plane gateway's TCP port: ``distributed.plane_port`` when set,
    else health port + 1 (one launcher knob covers all three planes)."""
    port = int(dist_args.get("plane_port") or 0)
    if port:
        return port
    from ..parallel.health import resolve_health_port

    return resolve_health_port(dist_args) + 1


def _pack_tree(tree) -> bytes:
    """Nested-dict tree of arrays -> npz bytes, keys = joined dict paths.

    Dict-only on purpose: params and record batches are dict trees, and a
    self-describing dict flattening means neither side needs to ship a
    treedef over the wire.  Raises on any other container so a structure
    this cannot round-trip fails loudly at the sender."""
    flat: Dict[str, np.ndarray] = {}

    def walk(node, path: str) -> None:
        if isinstance(node, dict):
            for k, v in node.items():
                if "\x1f" in str(k):
                    raise ValueError(f"tree key {k!r} contains the path separator")
                walk(v, path + "\x1f" + str(k) if path else str(k))
            return
        if isinstance(node, (list, tuple)):
            raise ValueError(
                "plane transport trees must be nested dicts of arrays "
                f"(got {type(node).__name__} at {path!r})"
            )
        # graftlint: allow[HS001] reason=serialization IS the host crossing: these bytes leave the machine over DCN, and callers run this off the trainer hot loop (gateway serve thread / actor-host loop)
        flat[path] = np.asarray(_local_view(node))

    walk(tree, "")
    buf = io.BytesIO()
    np.savez(buf, **flat)
    return buf.getvalue()


def _unpack_tree(payload: bytes) -> Dict[str, Any]:
    """Inverse of _pack_tree: npz bytes -> nested dict of numpy arrays."""
    out: Dict[str, Any] = {}
    with np.load(io.BytesIO(payload)) as z:
        for key in z.files:
            node = out
            parts = key.split("\x1f")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = z[key]
    return out


def _send_msg(wfile, header: Dict[str, Any], payload: bytes = b"") -> int:
    """One header line + optional payload; returns bytes written."""
    header = dict(header, nbytes=len(payload))
    line = (json.dumps(header) + "\n").encode()
    wfile.write(line + payload)
    wfile.flush()
    return len(line) + len(payload)


def _recv_msg(rfile) -> Tuple[Optional[Dict[str, Any]], bytes, int]:
    """One (header, payload, bytes_read); header None on a closed peer."""
    line = rfile.readline()
    if not line:
        return None, b"", 0
    header = json.loads(line)
    n = int(header.get("nbytes", 0))
    payload = rfile.read(n) if n else b""
    if len(payload) != n:
        raise ConnectionError(
            f"plane transport: truncated payload ({len(payload)}/{n} bytes)"
        )
    return header, payload, len(line) + n


class PlaneGateway:
    """Learner-side plane server: versioned params out, records in.

    The trainer publishes through the same ``publish(params, version)``
    surface as ``PlaneParamCache`` (and delegates to one, ``inner``, when
    the learner also runs a local split plane) — publish stores a REFERENCE
    under the version lock and returns; the D2H + npz serialization happen
    lazily in the serving thread on the first actor poll of that version,
    off the trainer hot loop.  ``on_records`` receives each decoded host
    record tree on a serving thread; the learner's callback validates the
    lane count and ingests into the device rings.

    An actor-host disconnect after hello bumps ``actor_host_losses`` and
    the run CONTINUES — the remaining producers absorb the game quota
    (the epoch episode budget is global, so backpressure redistributes
    automatically).  ``stop()`` makes every subsequent request answer
    {"kind": "stop"} so actor hosts exit cleanly at run end.
    """

    def __init__(self, dist_args: Dict[str, Any],
                 on_records: Callable[[Dict[str, Any]], None],
                 inner: Optional[PlaneParamCache] = None):
        self._port = resolve_plane_port(dist_args)
        self.on_records = on_records
        self.inner = inner
        self._lock = threading.Lock()
        self._params = None          # newest published tree (reference)
        self._packed: Optional[Tuple[int, bytes]] = None  # lazy (version, npz)
        self.version = -1
        self.refreshes = 0
        self._stop = threading.Event()
        self._stopping = threading.Event()  # answer "stop" from here on
        self._server: Optional[socket.socket] = None
        self._threads: list = []
        self.bytes_in = 0
        self.bytes_out = 0
        self.record_batches = 0
        self.actor_hosts = 0         # currently connected (post-hello)
        self.actor_hosts_seen = 0
        self.actor_host_losses = 0

    # -- trainer-facing surface (PlaneParamCache duck type) ------------------

    def publish(self, params, version: int) -> None:
        version = int(version)
        if self.inner is not None:
            # local actor mesh first: monotonicity is enforced there and a
            # raise must leave the gateway untouched too
            self.inner.publish(params, version)
        with self._lock:
            if self.inner is None and version <= self.version:
                raise ValueError(
                    f"param version must advance monotonically: "
                    f"{version} <= {self.version}"
                )
            self._params = params
            self.version = version
            self.refreshes += 1
            self._packed = None      # serialized lazily on next poll

    def latest(self):
        if self.inner is not None:
            return self.inner.latest()
        with self._lock:
            if self._params is None:
                raise RuntimeError("PlaneGateway.latest() before first publish")
            return self.version, self._params

    def lag(self, learner_steps: int) -> int:
        return max(0, int(learner_steps) - self.version) if self.refreshes else 0

    @property
    def bytes_transferred(self) -> int:
        with self._lock:
            inner = self.inner.bytes_transferred if self.inner is not None else 0
        return self.bytes_in + self.bytes_out + inner

    def _packed_params(self) -> Tuple[int, bytes]:
        """(version, npz bytes) of the newest publish, serialized at most
        once per version — on a serving thread, never the trainer's."""
        with self._lock:
            if self._packed is not None and self._packed[0] == self.version:
                return self._packed
            version, params = self.version, self._params
        with trace_span("plane.param_publish", version=version):
            payload = _pack_tree(params)
        with self._lock:
            if self._packed is None or self._packed[0] < version:
                self._packed = (version, payload)
            return self._packed

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        self._server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._server.bind(("", self._port))
        self._server.listen(8)
        self._server.settimeout(0.5)
        t = threading.Thread(
            target=self._accept_loop, daemon=True, name="plane-gateway-accept"
        )
        t.start()
        self._threads.append(t)
        print(f"plane gateway: listening on port {self._port}")

    def begin_stop(self) -> None:
        """Run concluding: answer every further request with a clean stop
        (actor hosts exit 0) but keep serving until stop()."""
        self._stopping.set()

    def stop(self) -> None:
        self._stopping.set()
        self._stop.set()
        server, self._server = self._server, None
        if server is not None:
            try:
                server.close()
            except OSError:
                pass

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            server = self._server
            if server is None:
                return
            try:
                conn, _addr = server.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            t = threading.Thread(
                target=self._serve, args=(conn,), daemon=True,
                name="plane-gateway-serve",
            )
            t.start()
            self._threads.append(t)

    def _serve(self, conn: socket.socket) -> None:
        import sys

        conn.settimeout(300.0)
        rfile = conn.makefile("rb")
        wfile = conn.makefile("wb")
        hello = False
        try:
            while not self._stop.is_set():
                header, payload, n_in = _recv_msg(rfile)
                if header is None:
                    break   # peer closed
                with self._lock:
                    self.bytes_in += n_in
                if self._stopping.is_set():
                    _send_msg(wfile, {"kind": "stop"})
                    hello = False   # clean goodbye, not a loss
                    break
                kind = header.get("kind")
                if kind == "hello":
                    hello = True
                    with self._lock:
                        self.actor_hosts += 1
                        self.actor_hosts_seen += 1
                    print(
                        "plane gateway: actor host connected "
                        f"({header.get('host', '?')}, "
                        f"{self.actor_hosts} live)"
                    )
                    n = _send_msg(wfile, {"kind": "ok", "version": self.version})
                elif kind == "records":
                    with trace_span("plane.record_xfer",
                                    nbytes=len(payload), direction="in"):
                        records = _unpack_tree(payload)
                        self.on_records(records)
                    with self._lock:
                        self.record_batches += 1
                    n = _send_msg(wfile, {"kind": "ok", "version": self.version})
                elif kind == "params":
                    have = int(header.get("have", -1))
                    version, packed = (
                        self._packed_params()
                        if self.version > have and self._params is not None
                        else (self.version, b"")
                    )
                    n = _send_msg(
                        wfile, {"kind": "params", "version": version},
                        packed if version > have else b"",
                    )
                else:
                    n = _send_msg(
                        wfile, {"kind": "error", "error": f"unknown kind {kind!r}"}
                    )
                with self._lock:
                    self.bytes_out += n
        except (OSError, ValueError, ConnectionError) as e:
            if not self._stop.is_set():
                print(
                    f"[handyrl_tpu] plane gateway: actor connection error: {e}",
                    file=sys.stderr,
                )
        finally:
            if hello:
                with self._lock:
                    self.actor_hosts -= 1
                    if not self._stopping.is_set():
                        # a loss, not a goodbye: throughput degrades, the
                        # run continues (the degradable direction of the
                        # fault matrix)
                        self.actor_host_losses += 1
                        print(
                            "[handyrl_tpu] plane gateway: actor host LOST "
                            f"({self.actor_hosts} live; survivors absorb "
                            "its game quota)",
                            file=sys.stderr,
                        )
            for f in (rfile, wfile):
                try:
                    f.close()
                except OSError:
                    pass
            try:
                conn.close()
            except OSError:
                pass


class PlaneClient:
    """Actor-host side of the plane gateway protocol.

    One blocking request/reply socket per actor host (the rollout loop is
    itself serial: generate -> ship -> maybe refresh params).  Methods
    return None once the gateway said "stop" (clean run end); a dead
    socket raises ConnectionError — the actor host's loop announces the
    lost learner loudly and exits 75 (resumable: a relaunched learner is
    reconnectable).
    """

    def __init__(self, dist_args: Dict[str, Any], timeout: float = 300.0):
        from ..parallel.health import _split_address

        self._host = _split_address(dist_args["coordinator_address"])[0]
        self._port = resolve_plane_port(dist_args)
        self._timeout = float(timeout)
        self._sock: Optional[socket.socket] = None
        self._rfile = None
        self._wfile = None
        self._lock = threading.Lock()
        self.bytes_in = 0
        self.bytes_out = 0
        self.param_version = -1
        self.stopped = False

    def connect(self, retry_for: float = 60.0) -> int:
        """Dial the gateway (retrying — the learner may still be
        compiling), send hello, return the gateway's param version."""
        deadline = time.monotonic() + float(retry_for)
        last: Optional[Exception] = None
        while time.monotonic() < deadline:
            try:
                sock = socket.create_connection(
                    (self._host, self._port), timeout=self._timeout
                )
                break
            except OSError as e:
                last = e
                time.sleep(1.0)
        else:
            raise ConnectionError(
                f"plane gateway at {self._host}:{self._port} unreachable "
                f"for {retry_for:.0f}s: {last}"
            )
        sock.settimeout(self._timeout)
        self._sock = sock
        self._rfile = sock.makefile("rb")
        self._wfile = sock.makefile("wb")
        import platform

        reply, _payload = self._roundtrip(
            {"kind": "hello", "host": platform.node()}
        )
        if reply is None:
            return -1
        self.param_version = int(reply.get("version", -1))
        return self.param_version

    def _roundtrip(self, header: Dict[str, Any], payload: bytes = b""):
        """(reply header, reply payload); None header once stopped."""
        with self._lock:
            if self.stopped:
                return None, b""
            self.bytes_out += _send_msg(self._wfile, header, payload)
            reply, rpayload, n_in = _recv_msg(self._rfile)
            self.bytes_in += n_in
            if reply is None:
                raise ConnectionError("plane gateway closed the connection")
            if reply.get("kind") == "stop":
                self.stopped = True
                return None, b""
            if reply.get("kind") == "error":
                raise ConnectionError(f"plane gateway: {reply.get('error')}")
            return reply, rpayload

    def ship_records(self, records: Dict[str, Any]) -> Optional[int]:
        """Send one host record tree; returns the gateway's current param
        version (the poll hint), or None once the run is stopping."""
        with trace_span("plane.record_xfer", direction="out"):
            payload = _pack_tree(records)
            reply, _ = self._roundtrip({"kind": "records"}, payload)
        if reply is None:
            return None
        return int(reply.get("version", -1))

    def poll_params(self, have: Optional[int] = None):
        """(version, params-or-None): params bytes come back only when the
        gateway holds a newer version than ``have`` (default: the newest
        this client has seen).  Returns None once the run is stopping."""
        have = self.param_version if have is None else int(have)
        reply, payload = self._roundtrip({"kind": "params", "have": have})
        if reply is None:
            return None
        version = int(reply.get("version", -1))
        if not payload:
            return version, None
        self.param_version = version
        return version, _unpack_tree(payload)

    def close(self) -> None:
        with self._lock:
            for f in (self._rfile, self._wfile):
                try:
                    if f is not None:
                        f.close()
                except OSError:
                    pass
            if self._sock is not None:
                try:
                    self._sock.close()
                except OSError:
                    pass
            self._sock = self._rfile = self._wfile = None
