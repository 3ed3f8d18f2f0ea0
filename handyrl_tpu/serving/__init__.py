"""The standalone inference serving plane (docs/serving.md).

Layered on the pieces the training stack already proved out: jitted
numpy-in/out ``InferenceModel``s, manifest-verified snapshot loading,
the framed-socket transport with per-peer bounded send queues, and the
per-device dispatch-lock registry.

* ``ContinuousBatcher`` — iteration-level batched inference with
  per-request deadlines and SLO-driven load shedding.
* ``ModelRouter`` — N resident snapshot engines + ensemble routes,
  zero-downtime warm-then-flip hot-swap.
* ``ServingServer`` / ``ServingClient`` — the network front and its
  pipelined client.
"""

from .batcher import (
    BadRequest,
    ContinuousBatcher,
    DeadlineExceeded,
    RequestShed,
    ServeError,
)
from .client import ServingClient, ServingError
from .router import EnsembleRoute, ModelRouter, RouteError
from .server import ServingServer, build_serving, serve_main

__all__ = [
    "BadRequest",
    "ContinuousBatcher",
    "DeadlineExceeded",
    "RequestShed",
    "ServeError",
    "ServingClient",
    "ServingError",
    "EnsembleRoute",
    "ModelRouter",
    "RouteError",
    "ServingServer",
    "build_serving",
    "serve_main",
]
