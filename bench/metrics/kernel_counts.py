"""Operations and bytes each service kernel's algorithm needs for one
call, from the call's shapes alone (not from how the kernel tiles it).
Each returns ``(flops, bytes)``; bytes count what the call must read
from and write to device memory at its own dtypes."""
from __future__ import annotations

DPI_DIMS = (64, 128, 64, 1)        # beat bytes -> hidden -> hidden -> score
BEAT = 64


def aes_ecb(n_bytes: int):
    """AES-128-ECB over ``n_bytes``: bytes in and out, no matrix work
    (S-box lookups and GF(2^8) shifts count as no FLOPs)."""
    return 0, 2 * n_bytes


def dpi_mlp(n_pkts: int, mtu: int):
    """Ternary 64-128-64-1 MLP on every 64-byte beat: two FLOPs per
    multiply-add; payload bytes in, one float32 score per beat out,
    int8 weights read once."""
    beats = n_pkts * (mtu // BEAT)
    macs = sum(a * b for a, b in zip(DPI_DIMS, DPI_DIMS[1:]))
    weights = macs + DPI_DIMS[1] * 4 + DPI_DIMS[2] * 4
    return 2 * macs * beats, n_pkts * mtu + 4 * beats + weights


def preproc(n_records: int, rec_words: int):
    """Neg2Zero, Log and Modulus over int32 records: read and write every
    word once; elementwise, so no FLOPs are counted."""
    return 0, 2 * 4 * n_records * rec_words


def crc32(n_pkts: int, mtu: int):
    """ICRC over every payload byte: bytes in, one int32 per packet out."""
    return 0, n_pkts * mtu + 4 * n_pkts


def chunk_reduce(n_src: int, n_elems: int, itemsize: int = 4):
    """Left fold of ``n_src`` equal chunks: ``n_src - 1`` adds per
    element; every source read once, the result written once."""
    return (n_src - 1) * n_elems, (n_src + 1) * n_elems * itemsize


def need(kernel: str, calls):
    """Summed ``(flops, bytes)`` over the calls recorded for a kernel."""
    fn = globals()[kernel]
    flops = nbytes = 0
    for args in calls:
        f, b = fn(*args)
        flops += f
        nbytes += b
    return flops, nbytes
