"""Operations and bytes of the kernels, from shapes alone.

The benchmark's yardstick: a change to the program cannot change these.
A model's own counts (a token's operations, the KV bytes a decode lane
reads) follow its family's sizes, in ``bench/families/<family>/counts.py``.

  * ``mpmm``: one quantized matmul call (M x K) @ (K x N): 2*M*N*K integer
    operations; bytes are the packed input codes, the packed weight codes
    and the f32 output, each moved once; ``mpmm_roofline`` sums the least
    time of every traced call from its own shapes.
"""

from __future__ import annotations


def mpmm(M: int, N: int, K: int, x_bits: int, w_bits: int) -> tuple[int, int]:
    """(operations, bytes) of one mpmm call."""
    return 2 * M * N * K, M * K * x_bits // 8 + N * K * w_bits // 8 + 4 * M * N


def mpmm_roofline(calls, peaks: dict):
    """% of roofline over traced mpmm calls: (kernel name, device seconds,
    [(dtype, shape)] of result and operands) as ``trace_reduce`` gives them.
    None when no mpmm call was traced."""
    least = dev = 0.0
    for name, dur, shapes in calls:
        if not name.startswith("mpmm_u"):
            continue
        xb, wb = int(name.split("_")[1][1:]), int(name.split("_")[2][1:])  # mpmm_u8_i4_u8
        (_, (M, N)), (_, (_, kx)) = shapes[0], shapes[1]
        ops, nbytes = mpmm(M, N, kx * 8 // xb, xb, wb)
        least += max(ops / peaks["int8_ops"], nbytes / peaks["hbm_bw"])
        dev += dur
    return 100.0 * least / dev if dev else None
