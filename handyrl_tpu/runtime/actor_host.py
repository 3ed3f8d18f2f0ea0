"""Dedicated actor host: on-device self-play feeding a remote learner.

Pod-slice rung 2 (docs/performance.md §Pod-slice topology).  A process
launched with ``distributed.role: actor`` runs ONLY the data plane: the
streaming device rollout over all of its local devices, shipping each
(K, B, ...) record batch to the learner's plane gateway over DCN and
polling versioned params back (runtime/plane.py — the health plane's TCP
framing with byte-counted npz payloads).

Deliberately OUTSIDE ``jax.distributed``: an actor host never joins the
learner collective, so losing one can never wedge a cross-host train step
— the learner's gateway logs the disconnect, bumps
``dist_actor_host_losses``, and the surviving producers absorb the game
quota (the degradable direction of docs/fault_tolerance.md's matrix).
The reverse is loud: a dead gateway socket means the learner tier is
gone, and this process announces the fault and exits 75 (EX_TEMPFAIL) so
a supervisor relaunches it once the learner is back — the params it
would generate against are unowned until then.

``actor_host_main`` is the process: the tracer, the signals, the exit code.
``actor_loop`` is the plane itself, over the devices its caller hands it
(``jax.local_devices()`` there; the benchmark's ``actor_stream`` runner hands
it one chip and a loopback gateway).  Parameters live on the device in the
dtype the module makes them in (``param_dtype``: a bfloat16 acting copy is
never preceded by a float32 tree) and a polled tree is cast to that and put
there once, when it is installed.  Spans on the loop's thread:
``actor.dispatch``, ``actor.fetch``, ``actor.ship``, ``actor.poll``; events
``actor.weights`` (parameters, bytes, dtype, when a tree is made or
installed) and ``actor.counters`` (what the module's step mode counted over
a dispatch; docs/observability.md).

Rate coupling is structural: one record batch is in flight per host (the
ship is a blocking request/reply), so a slow learner back-pressures the
rollout loop without a budget protocol.
"""

from __future__ import annotations

import signal
import sys
import threading
import time
from typing import Any, Dict, Optional, Sequence

from ..envs import make_env, prepare_env
from ..utils import trace
from ..utils.retry import retry_call
from ..utils.trace import trace_event, trace_phase, trace_phase_since, trace_span

# same convention as the learner's drain path (runtime/learner.py)
EXIT_RESUMABLE = 75


class GatewayLost(ConnectionError):
    """The plane gateway's socket died under the loop: the learner tier is gone."""


def actor_host_main(args: Dict[str, Any]) -> None:
    """Entry point for ``--train`` with ``distributed.role: actor``."""
    import jax

    dist = dict(args["train_args"].get("distributed") or {})
    rank = int(dist.get("process_id") or 0)
    if trace.configure(args["train_args"].get("trace"), rank=1000 + rank):
        print(f"trace: spans -> {trace.current_path()} (actor host {rank})")

    stop = threading.Event()

    def _stop_signal(signum, frame):
        print(
            f"[handyrl_tpu] actor host {rank}: signal {signum} — draining",
            file=sys.stderr,
        )
        stop.set()

    signal.signal(signal.SIGTERM, _stop_signal)
    signal.signal(signal.SIGINT, _stop_signal)
    try:
        done = actor_loop(args, jax.local_devices(), stop)
    except GatewayLost as e:
        from ..parallel.health import announce_fault

        announce_fault(str(e), "learner_loss", EXIT_RESUMABLE)
        sys.exit(EXIT_RESUMABLE)
    print(f"actor host {rank}: finished ({done['dispatches']} dispatches)")


def _weights_event(params) -> None:
    import jax

    leaves = jax.tree.leaves(params)
    trace_event(
        "actor.weights", 0.0,
        parameters=int(sum(x.size for x in leaves)),
        bytes=int(sum(x.size * x.dtype.itemsize for x in leaves)),
        dtype=",".join(sorted({x.dtype.name for x in leaves})),
    )


def _install(fresh, params, sharding):
    """A polled host tree, leaf by leaf: cast on the host to the dtype the
    held leaf has, put where it lies, and only then let the held leaf go (two
    whole trees of a large net do not fit a chip).  No dispatch is in flight:
    the loop fetched its records before it polled."""
    import jax
    import numpy as np

    def one(new, old):
        new = jax.device_put(np.asarray(new).astype(old.dtype), sharding)
        old.delete()
        return new

    return jax.tree.map(one, fresh, params)


def actor_loop(args: Dict[str, Any], devices: Sequence[Any],
               stop: threading.Event) -> Dict[str, Any]:
    """The actor plane over ``devices`` until ``stop`` is set or the gateway
    says so: dispatch the streaming rollout, fetch its records, ship them,
    poll parameters when the gateway holds newer ones.  Returns what it ends
    with: ``dispatches`` and the ``params`` it last acted on."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from ..ops import ssd
    from ..parallel.mesh import dispatch_serialized, make_mesh
    from .plane import PlaneClient
    from .rollout_plane import Lanes, vector_env_of

    train_args = dict(args["train_args"])
    train_args["env"] = args["env_args"]
    dist = dict(train_args.get("distributed") or {})
    seed = int(train_args["seed"])
    rank = int(dist.get("process_id") or 0)
    prepare_env(args["env_args"])
    env = make_env(args["env_args"])
    module = env.net()
    # match the learner tier's PER-PROCESS lane count: the gateway ingests
    # into rings built for device_rollout_games / num_processes lanes
    # (config.py validated the divisibility), and a mismatched record
    # batch width must fail loudly at the gateway, not silently reshape
    games = int(train_args["device_rollout_games"]) // max(
        1, int(dist.get("num_processes") or 1)
    )
    mesh = make_mesh({"dp": -1}, list(devices))
    lanes = Lanes(
        vector_env_of(env, train_args, mesh, games, streaming=True),
        module, train_args, mesh, games, counters=True,
    )
    # identical seed -> identical init params on every process: rollouts
    # are on-policy-ish from step 0, before the first param poll lands.
    # One jitted call of the module's own initialisers, onto the devices,
    # in the dtype the module makes its parameters in
    replicated = NamedSharding(mesh, PartitionSpec())
    env.reset()
    sample = jax.tree.map(lambda x: x[None], env.observation(env.players()[0]))
    make = jax.jit(
        lambda key: module.init(key, sample, module.initial_state((1,)))["params"],
        out_shardings=replicated,
    )
    with trace_phase("setup.actor_weights", plane="actor"):
        # graftlint: allow[HS001] reason=set-up, once a process: the phase ends when the seeded weights are on the device, before the loop's first dispatch
        params = jax.block_until_ready(
            dispatch_serialized(lambda: make(jax.random.PRNGKey(seed)), mesh))
    _weights_event(params)

    client = PlaneClient(dist)
    version = client.connect(
        retry_for=float(dist.get("initialization_timeout") or 300.0)
    )
    print(
        f"actor host {rank}: connected to plane gateway "
        f"(param version {version}); {games} lanes on {mesh.size} devices"
    )

    def _reconnect(i, exc):
        # one flaky syscall (EINTR, a reset mid-frame) must not cost an
        # exit 75: drop the wedged connection, dial a fresh one, and let
        # retry_call re-issue the SAME request.  A reconnect that itself
        # fails propagates — that IS the gateway being gone, and the
        # outer handler's announce_fault + exit 75 keeps its meaning
        nonlocal client
        print(
            f"[handyrl_tpu] actor host {rank}: transient plane fault "
            f"({exc}); reconnect attempt {i + 1}",
            file=sys.stderr,
        )
        try:
            client.close()
        except Exception:
            pass
        client = PlaneClient(dist)
        client.connect(retry_for=30.0)

    # rank-decorrelated rollout stream, offset past the learner ranks'
    # seed + 1009*rank family so a co-hosted learner never shares a key; the
    # first dispatch places key, state and hidden where its program runs
    stream = lanes.stream(
        jax.random.PRNGKey(seed + 0x5EED + 0xAC706 + 1009 * rank)
    )
    dispatches, written = 0, set()
    # the phase ``setup.actor_first_dispatch``: from here to the first
    # dispatch's fetched records (the rollout program traced, lowered, loaded
    # or built, and run once); None from then on
    t_first: Optional[float] = time.monotonic()
    try:
        while not stop.is_set():
            records, counted = stream.step(params, trace_span("actor.dispatch"))
            with trace_span("actor.fetch"):
                # graftlint: allow[HS001] reason=the record batch leaves this machine over DCN — host materialization is the transport's input, one D2H per k_steps block
                host_records, counted = jax.device_get((records, counted))
            if t_first is not None:
                trace_phase_since("setup.actor_first_dispatch", t_first, plane="actor")
                t_first = None
            if counted:     # host scalars by now: fetched with the records
                trace_event("actor.counters", 0.0,
                            **{name: value.tolist() for name, value in counted.items()})
            if trace.enabled() and len(written) < len(ssd.ROW_PATHS):
                # how the program steps a (lane, player) state's acting rows:
                # chosen from dtype and shape when it was traced, once each
                for chosen in set(ssd.ROW_PATHS) - written:
                    trace_event("model.ssd_rows_path", 0.0, **ssd.ROW_PATHS[chosen])
                    written.add(chosen)
            with trace_span("actor.ship"):
                gateway_version = retry_call(
                    lambda: client.ship_records(host_records),
                    attempts=3, base_delay=0.1, on_retry=_reconnect,
                )
            if gateway_version is None:
                break  # clean stop from the gateway
            dispatches += 1
            if gateway_version > client.param_version:
                with trace_span("actor.poll"):
                    got = retry_call(
                        lambda: client.poll_params(),
                        attempts=3, base_delay=0.1, on_retry=_reconnect,
                    )
                    if got is None:
                        break
                    new_version, fresh = got
                    if fresh is not None:
                        params = _install(fresh, params, replicated)
                        _weights_event(params)
                        print(
                            f"actor host {rank}: params -> version {new_version}"
                        )
    except (ConnectionError, OSError) as e:
        raise GatewayLost(
            f"plane gateway lost after {dispatches} dispatches: {e}"
        ) from e
    finally:
        stream.drain()
        client.close()
    return {"dispatches": dispatches, "params": params}
