"""Required work of a round and of each kernel call, from logical shapes.

Every count here is what the algorithm needs, not what an implementation
happens to move: the rows a call touches (not the 8-row groups a kernel
fetches), the training steps the task's own data defines, each operand
read once and each result written once.  So no honest implementation
can read above 100% of a roofline built from these numbers.
"""
from __future__ import annotations

F32, I8, MASK = 4, 1, 1


# -- the paper CNN -------------------------------------------------------------

def cnn_layer_macs(side: int = 28, c1: int = 20, c2: int = 50,
                   hidden: int = 128, classes: int = 10, k: int = 5):
    """Multiply-accumulates per image of each layer of the paper CNN:
    two SAME ``k``x``k`` convolutions (1->c1 on side x side, c1->c2 after a
    2x2 pool), a 2x2 pool, then fc(hidden) and fc(classes)."""
    s2, s4 = side // 2, side // 4
    return {
        'conv1': side * side * c1 * k * k * 1,
        'conv2': s2 * s2 * c2 * k * k * c1,
        'fc1': s4 * s4 * c2 * hidden,
        'fc2': hidden * classes,
    }


def cnn_forward_macs(**kw) -> int:
    return sum(cnn_layer_macs(**kw).values())


def cnn_train_flops_per_image(**kw) -> int:
    """Forward, plus the backward pass: a weight gradient for every layer
    and an input gradient for every layer but the first (the images are
    not differentiated).  2 FLOPs per multiply-accumulate."""
    macs = cnn_layer_macs(**kw)
    fwd = sum(macs.values())
    return 2 * (3 * fwd - macs['conv1'])


def supervised_round_flops(m: int, nb: int, batch: int, epochs: int,
                           flops_per_image: int) -> int:
    """Training FLOPs of one round: every client's [nb, batch] batches,
    ``epochs`` times (the steps the task's data defines)."""
    return m * nb * batch * epochs * flops_per_image


# -- aggregation kernels ---------------------------------------------------------

def dense_aggregate_bytes(m: int, n: int) -> int:
    """Dense Eq. 6-8 over [m, n]: read cache, trained and the global, the
    three role masks and the weights; write the new global and the new
    cache."""
    return F32 * (3 * m * n + 2 * n) + 3 * MASK * m + F32 * m


def rows_bytes(k: int, n: int) -> int:
    """Gather (or scatter) of k rows of width n: read k rows, write k."""
    return 2 * F32 * k * n


def quantize_bytes(k: int, n: int, qblock: int = 128) -> int:
    """Block int8 quantisation of k f32 rows: read them, write the int8
    rows and one f32 scale per ``qblock`` values."""
    return F32 * k * n + I8 * k * n + F32 * k * (n // qblock)


def tier_q8_bytes(k_up: int, k_cache: int, n: int, qblock: int = 128) -> int:
    """Fused int8 tier-rows Eq. 6-8: read ``k_up`` int8 uploads and their
    scales, read and write the ``k_cache`` cache entries that change, and
    read and write the global and the running aggregate."""
    return (I8 * k_up * n + F32 * k_up * (n // qblock)
            + 2 * F32 * k_cache * n + 4 * F32 * n)


def round_state_bytes(k_read: int, k_cache: int, n: int) -> int:
    """Least HBM traffic of the SAFA state in one round: read the
    ``k_read`` local models that training starts from, write the
    ``k_cache`` cache entries that change, and read and write the global
    and the running aggregate.  Training, the wire and the aggregation can stay in
    registers, so nothing else has to reach HBM."""
    return F32 * (k_read + k_cache) * n + 4 * F32 * n
