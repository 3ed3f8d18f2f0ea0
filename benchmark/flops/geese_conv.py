"""Operations and bytes one GeeseNet update needs, from shapes.

Counted: the multiply-adds of the convolutions and the two heads (2 FLOP
each), forward once and backward twice (gradient to the input and to the
weights), for every observation of the batch.  Not counted: GroupNorm,
ReLU, the loss, the optimizer, the replay sample: elementwise work a
roofline charges to bytes, not to the MXU.

Bytes: the least HBM traffic of an update that keeps nothing on the chip
between passes: every conv layer's activations written once forward and
read once backward in the compute type, the observations read once, and
the parameters with Adam's two moments read and written once in float32.
"""

BOARD = 7 * 11
IN_PLANES = 17
ACTIONS = 4


def forward_macs(filters: int, blocks: int) -> int:
    """Multiply-adds of one forward pass on one observation."""
    stem = BOARD * 9 * IN_PLANES * filters
    tower = blocks * BOARD * 9 * filters * filters
    heads = filters * ACTIONS + 2 * filters * 1
    return stem + tower + heads


def parameters(filters: int, blocks: int) -> int:
    convs = 9 * IN_PLANES * filters + blocks * 9 * filters * filters
    norms = (blocks + 1) * 2 * filters
    return convs + norms + filters * ACTIONS + 2 * filters


def train_update(config, cell):
    net = config["net"]
    train = cell["train_args"]
    filters, blocks = int(net["filters"]), int(net["blocks"])
    observations = (int(train["batch_size"]) * int(train["forward_steps"])
                    * int(cell.get("players_per_window", 1)))
    flops = 2 * 3 * forward_macs(filters, blocks) * observations
    act_bytes = 4       # float32 compute
    activations = observations * BOARD * filters * (blocks + 1) * act_bytes * 2
    inputs = observations * BOARD * IN_PLANES * act_bytes
    state = parameters(filters, blocks) * 4 * 3 * 2
    return {"flops": float(flops), "bytes": float(activations + inputs + state),
            "observations": observations}
