"""Operations and bytes of the program's sequence-model ops, computed from
their shapes: the numerator of a kernel's roofline share.

``ssd`` is Mamba-2's selective state-space scan as the program computes
it (``repro.core.ir``'s ``ssd`` op): the sequence cut into chunks of
``chunk`` tokens (the whole sequence when it is shorter), padded to whole
chunks.  Per chunk of L tokens, with H heads of P channels and one group
of state size N, its multiply-accumulates are

* C.B^T over the group: L * L * N;
* the masked intra-chunk product of those scores with x: H * L * L * P;
* each chunk's final state, and the carried states' read-out at every
  token: 2 * H * L * P * N.

The elementwise work (softplus, the decays' exponentials, the masked
cumulative sums) is not counted, as a peak FLOP/s counts none of it.
Its bytes are what the op must move at the least: its inputs x (S, H*P),
dt (S, H), B and C (S, N) and its three (H,) parameters read once, and
its output (S, H*P) written once.

Nothing here imports the program.
"""

from __future__ import annotations


def ssd_macs(seq: int, heads: int, head_dim: int, state: int,
             chunk: int = 256) -> int:
    """Multiply-accumulates of one sequence's chunked ``ssd``."""
    n = min(chunk, seq)
    chunks = -(-seq // n)
    return chunks * (n * n * state
                     + heads * n * n * head_dim
                     + 2 * heads * n * head_dim * state)


def ssd_flops(seq: int, heads: int, head_dim: int, state: int,
              chunk: int = 256) -> int:
    """Two FLOPs a multiply-accumulate."""
    return 2 * ssd_macs(seq, heads, head_dim, state, chunk)


def ssd_bytes(seq: int, heads: int, head_dim: int, state: int,
              itemsize: int = 4) -> int:
    """Bytes one sequence's ``ssd`` reads and writes at the least."""
    channels = heads * head_dim
    read = seq * (channels + heads + 2 * state) + 3 * heads
    return itemsize * (read + seq * channels)
