"""Serving-plane client: pipelined request/reply over one framed socket.

One connection, many outstanding requests: every frame carries a ``rid``
and a single receiver thread resolves the matching future, so a caller
can keep a submit window open (what a load generator does) or use
the blocking ``infer`` facade.  Server-side sheds and deadline misses
surface as ``ServingError`` with the wire ``kind`` — fast-fail reaches
the caller as an exception, never as a hang.

Liveness: ``stall_timeout`` arms the framed transport's stall deadline
on the receive side — a peer that keeps the socket open but stops
sending bytes while requests are pending fails every pending future
with ``ServingError(kind="stalled")`` instead of hanging them until
their per-call timeouts.  An idle connection (nothing pending) is never
reaped: request/reply clients are legitimately bursty.

Desync visibility: a reply frame whose ``rid`` is missing or unknown
(a confused or misbehaving server) is COUNTED (``replies_orphaned``)
and warned about once, instead of being silently dropped.

Sessions (docs/serving.md §Fleet tier): ``open_session`` pins recurrent
hidden state server-side; ``submit(..., sid=...)`` then carries only the
observation — the ship-hidden-state-both-ways path stays available as
the stateless fallback.
"""

from __future__ import annotations

import socket
import sys
import threading
from concurrent.futures import Future
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..runtime.connection import connect_socket_connection
from ..utils import tree_map

__all__ = ["ServingClient", "ServingError"]


class ServingError(RuntimeError):
    """Server-reported request failure; ``kind`` is the wire tag
    (shed / deadline / stopped / bad_request / swap_failed / stalled /
    replica_lost / ...)."""

    def __init__(self, kind: str, msg: str):
        super().__init__(f"[{kind}] {msg}")
        self.kind = kind


class ServingClient:
    def __init__(self, host: str, port: int, timeout: float = 60.0,
                 retry_seconds: float = 0.0,
                 stall_timeout: Optional[float] = None,
                 on_notice=None):
        self.conn = connect_socket_connection(
            host, int(port), timeout=timeout, retry_seconds=retry_seconds
        )
        self.stall_timeout = (
            None if not stall_timeout else float(stall_timeout)
        )
        # server-pushed notice frames (rid-less by design — e.g. the
        # "draining" broadcast a preempted replica sends every peer):
        # delivered here instead of the orphan counter.  Called on the
        # receiver thread, so handlers must only hand off, never block
        self.on_notice = on_notice
        self._lock = threading.Lock()
        self._pending: Dict[int, Future] = {}
        self._rid = 0
        self._closed = False
        self.replies_orphaned = 0
        self._orphan_warned = False
        self._recv_thread = threading.Thread(
            target=self._recv_loop, daemon=True, name="serve-client-recv"
        )
        self._recv_thread.start()

    # -- plumbing -----------------------------------------------------------

    def _recv_loop(self) -> None:
        while True:
            try:
                kind, data = self.conn.recv(timeout=self.stall_timeout)
            except socket.timeout:
                # the transport's stall deadline fired: no bytes for
                # stall_timeout.  With nothing pending that is just an
                # idle connection — keep listening (the gap deadline
                # consumed no partial frame, so the stream stays synced).
                # With requests pending the peer is wedged: fail them
                # all loudly and close — the stream may now be mid-frame
                with self._lock:
                    n_pending = len(self._pending)
                if n_pending == 0:
                    continue
                self._fail_all(ServingError(
                    "stalled",
                    f"server sent no bytes for {self.stall_timeout:.1f}s "
                    f"with {n_pending} request(s) pending",
                ))
                self.conn.close()
                return
            except Exception:
                self._fail_all(ConnectionResetError("serving connection lost"))
                return
            if kind == "heartbeat" or kind == "__hb__":
                continue
            if kind == "draining":
                # a preempting server announcing its drain window: a
                # notice, not a reply — it must reach the hook (the fleet
                # router's session-handoff trigger) before orphan counting
                hook = self.on_notice
                if hook is not None:
                    try:
                        hook(kind, data if isinstance(data, dict) else {})
                    except Exception:
                        pass  # the receiver thread outlives any bad hook
                continue
            rid = (data or {}).get("rid") if isinstance(data, dict) else None
            with self._lock:
                fut = self._pending.pop(rid, None)
            if fut is None or fut.done():
                # missing/unknown/duplicate rid: a desynced or misbehaving
                # server must be visible, not silently absorbed
                self.replies_orphaned += 1
                if not self._orphan_warned:
                    self._orphan_warned = True
                    print(
                        f"serving client: orphaned reply frame "
                        f"(kind={kind!r}, rid={rid!r}) — counting in "
                        "replies_orphaned; further orphans are silent",
                        file=sys.stderr,
                    )
                continue
            if kind == "error":
                fut.set_exception(
                    ServingError(data.get("kind", "error"), data.get("msg", ""))
                )
            elif kind == "stats":
                fut.set_result(data.get("stats"))
            else:  # result / swapped / session / session_closed
                fut.set_result(data)

    def _fail_all(self, exc: Exception) -> None:
        with self._lock:
            pending, self._pending = dict(self._pending), {}
        for fut in pending.values():
            if not fut.done():
                fut.set_exception(exc)

    def _send(self, req: str, data: Dict[str, Any]) -> Future:
        fut: Future = Future()
        with self._lock:
            if self._closed:
                fut.set_exception(ConnectionResetError("client closed"))
                return fut
            self._rid += 1
            rid = self._rid
            self._pending[rid] = fut
        try:
            self.conn.send((req, dict(data, rid=rid)))
        except Exception as exc:
            with self._lock:
                self._pending.pop(rid, None)
            if not fut.done():
                fut.set_exception(exc)
        return fut

    # -- API ----------------------------------------------------------------

    def submit(self, obs, model=-1, hidden=None,
               slo_ms: Optional[float] = None,
               sid: Optional[str] = None) -> Future:
        """Async inference; resolves to {"model": served_id, "out": tree}.
        With ``sid`` the server reads/writes the session's hidden state —
        the wire carries neither direction of it."""
        data: Dict[str, Any] = {"model": model, "obs": obs}
        if hidden is not None:
            data["hidden"] = hidden
        if slo_ms is not None:
            data["slo_ms"] = float(slo_ms)
        if sid is not None:
            data["sid"] = sid
        return self._send("infer", data)

    def infer(self, obs, model=-1, hidden=None, slo_ms: Optional[float] = None,
              sid: Optional[str] = None,
              timeout: float = 60.0) -> Dict[str, Any]:
        return self.submit(obs, model, hidden, slo_ms, sid).result(timeout=timeout)

    def open_session(self, model=-1, timeout: float = 30.0) -> str:
        """Open a server-resident recurrent session; returns its sid."""
        reply = self._send("open_session", {"model": model}).result(timeout=timeout)
        return reply["sid"]

    def close_session(self, sid: str, timeout: float = 30.0) -> Dict[str, Any]:
        return self._send("close_session", {"sid": sid}).result(timeout=timeout)

    def stats(self, timeout: float = 30.0) -> Dict[str, Any]:
        return self._send("stats", {}).result(timeout=timeout)

    def swap(self, model_id: int, params=None, timeout: float = 300.0) -> Dict[str, Any]:
        """Hot-swap the served latest to ``model_id`` (params inline, or
        loaded digest-verified from the server's model dir when None).
        Blocks until the standby engine is warm and the flip happened."""
        data: Dict[str, Any] = {"id": int(model_id)}
        if params is not None:
            # the wire codec speaks numpy pytrees; a device-resident params
            # tree (fresh from a train step) converts here, once
            data["params"] = tree_map(np.asarray, params)
        return self._send("swap", data).result(timeout=timeout)

    def export_sessions(self, timeout: float = 60.0) -> Dict[str, Any]:
        """Pull the server's whole session cache (migration source side):
        {"sessions": {sid: numpy hidden tree}, "fresh": [...], "count"}.
        The server CLEARS its cache — ownership transfers to the caller."""
        return self._send("export_sessions", {}).result(timeout=timeout)

    def import_sessions(self, sessions: Dict[str, Any], fresh=(),
                        timeout: float = 60.0) -> Dict[str, Any]:
        """Hand migrated sessions to the successor replica (adopt —
        they land in its spill tier and restore bit-identically)."""
        return self._send("import_sessions", {
            "sessions": sessions or {}, "fresh": list(fresh),
        }).result(timeout=timeout)

    # -- data flywheel (docs/serving.md §Data flywheel) ----------------------

    def harvest_open(self, players, sids, timeout: float = 30.0) -> str:
        """Bind one game's per-player sessions into a harvest episode on
        the server; returns the harvest id.  ``players``/``sids`` are
        parallel lists — the server captures each sid's obs/policy/value
        at its own infer seams from here on."""
        reply = self._send("harvest_open", {
            "players": list(players), "sids": list(sids),
        }).result(timeout=timeout)
        return reply["hid"]

    def harvest_step(self, hid: str, actions, legal, rewards, turn,
                     timeout: float = 30.0) -> int:
        """Close one step with the client-side half: per-player sampled
        actions (None for non-movers), legal-action lists, rewards, and
        the turn player.  Call AFTER every acting player's infer reply
        arrived — the reply is the capture receipt.  Returns the step
        count so far."""
        reply = self._send("harvest_step", {
            "hid": hid, "actions": list(actions), "legal": list(legal),
            "rewards": list(rewards), "turn": turn,
        }).result(timeout=timeout)
        return reply["steps"]

    def harvest_close(self, hid: str, outcome, timeout: float = 60.0) -> bool:
        """Finalize the episode with per-player outcomes (None = the game
        was abandoned: the server counts a truncated drop).  Returns
        whether the episode was kept."""
        reply = self._send("harvest_close", {
            "hid": hid,
            "outcome": None if outcome is None else list(outcome),
        }).result(timeout=timeout)
        return reply["kept"]

    def harvest_pull(self, max_episodes: int = 64,
                     timeout: float = 60.0) -> Tuple[list, Dict[str, Any]]:
        """Drain up to ``max_episodes`` completed harvest episodes
        (ownership transfers) plus the server's harvest counters — the
        learner ingest loop's poll."""
        reply = self._send("harvest_pull", {
            "max": int(max_episodes),
        }).result(timeout=timeout)
        return reply.get("episodes") or [], reply.get("counts") or {}

    def report_outcome(self, model: int, outcome: float,
                       timeout: float = 30.0) -> None:
        """Book one finished game's outcome ([-1, 1]) against the epoch
        that served it — the promotion gate / quality sentinel's feed.
        Pin the game to one epoch (the first reply's served id) so the
        attribution is honest."""
        self._send("report_outcome", {
            "model": int(model), "outcome": float(outcome),
        }).result(timeout=timeout)

    def pending_count(self) -> int:
        """Requests in flight on this connection — the migration drain
        barrier (a retire exports only once this reaches zero)."""
        with self._lock:
            return len(self._pending)

    def wire_bytes(self) -> Tuple[int, int]:
        """(sent, received) frame bytes on this connection so far."""
        return self.conn.bytes_sent, self.conn.bytes_received

    def close(self) -> None:
        with self._lock:
            self._closed = True
        self.conn.close()
        self._fail_all(ConnectionResetError("client closed"))
