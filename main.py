"""CLI entry point — mode dispatch parity with reference main.py:8-38.

Modes:
    --train / -t             standalone training (learner + local actors)
    --train-server / -ts     learner serving remote TCP workers
    --worker / -w            worker machine connecting to a train server
    --serve / -s             standalone inference serving plane
                             (continuous batching + hot-swap; docs/serving.md;
                             SIGTERM drains sessions to the fleet and exits 75)
    --fleet / -f             fleet front-end: session-affinity router over
                             the replicas in fleet.replicas (docs/serving.md);
                             fleet.autoscale.enabled spawns/retires local
                             replica processes against the shed-rate SLO
    --edge [ARTIFACT]        CPU edge replica serving a frozen export
                             artifact (fleet capability tag: edge)
    --league / -l            population-based league training (PFSP
                             matchmaking + promotion gate; docs/league.md)
    --eval / -e              MODEL_PATH NUM_GAMES NUM_PROCESS
    --eval-server / -es      network battle server
    --eval-client / -ec      network battle client
"""

import sys

import yaml

from handyrl_tpu.config import normalize_args
from handyrl_tpu.utils import enable_compile_cache


def load_args(path: str = "config.yaml"):
    with open(path) as f:
        return normalize_args(yaml.safe_load(f) or {})


if __name__ == "__main__":
    enable_compile_cache()
    try:
        args = load_args()
    except FileNotFoundError:
        args = None
    print(sys.argv)

    if len(sys.argv) < 2:
        print("Please set mode of HandyRL-TPU.")
        sys.exit(1)

    mode = sys.argv[1]

    if mode in ("--train", "-t", "--train-server", "-ts"):
        dist = args["train_args"].get("distributed") or {}
        if dist.get("role") == "actor":
            # dedicated actor host (docs/performance.md §Pod-slice
            # topology): deliberately OUTSIDE jax.distributed — it talks
            # to the learner tier over the plane gateway only, so losing
            # it can never wedge the learner collective
            from handyrl_tpu.runtime.actor_host import actor_host_main

            actor_host_main(args)
        else:
            from handyrl_tpu.parallel import init_distributed

            init_distributed(dist)
            if mode in ("--train", "-t"):
                from handyrl_tpu.runtime.learner import train_main

                train_main(args)
            else:
                from handyrl_tpu.runtime.learner import train_server_main

                train_server_main(args)
    elif mode in ("--worker", "-w"):
        from handyrl_tpu.runtime.server import worker_main

        worker_main(args, sys.argv)
    elif mode in ("--serve", "-s"):
        from handyrl_tpu.serving import serve_main

        serve_main(args)
    elif mode in ("--fleet", "-f"):
        from handyrl_tpu.fleet import fleet_main

        fleet_main(args)
    elif mode == "--edge":
        from handyrl_tpu.fleet import edge_main

        if len(sys.argv) > 2:
            args["edge_model"] = sys.argv[2]
        edge_main(args)
    elif mode in ("--league", "-l"):
        from handyrl_tpu.league import league_main
        from handyrl_tpu.parallel import init_distributed

        init_distributed(args["train_args"].get("distributed"))
        league_main(args)
    elif mode in ("--eval", "-e"):
        from handyrl_tpu.runtime.evaluation import eval_main

        eval_main(args, sys.argv[2:])
    elif mode in ("--eval-server", "-es"):
        from handyrl_tpu.runtime.battle import eval_server_main

        eval_server_main(args, sys.argv[2:])
    elif mode in ("--eval-client", "-ec"):
        from handyrl_tpu.runtime.battle import eval_client_main

        eval_client_main(args, sys.argv[2:])
    else:
        print("Unknown mode %s" % mode)
        sys.exit(1)
