"""Traffic generators: every input a cell feeds the program, made on the
device from the run's seed.

Copied, as the yardstick, from the program's own generators and kept
here so that no later change to the program moves them:

- ``rx_trace`` is the in-sequence multi-QP header trace of
  ``benchmarks/fig6_multiqp._trace_batch``, with arrivals interleaved
  across QPs instead of sorted by QP, and PSNs that continue from one
  batch to the next;
- ``dpi_packets`` / ``dpi_dataset`` are ``repro.data.dpi_dataset``'s
  benign (text, CSV, PNG) and malicious (x86 opcode, ELF) 64-byte beats,
  with near-threshold beats (``edge_beats``) added;
- ``criteo_records`` / ``encode_packets`` are ``repro.data.synthetic``'s
  DLRM records and record-aligned packet layout, with the click label
  carried as the last word of each record.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

PSN_MASK = 0xFFFFFF
WRITE_ONLY = 0x0A            # InfiniBand RC opcode "RDMA WRITE Only"
BEAT = 64

_TEXT = np.frombuffer(b"etaoinshrdlucmfwypvbgkjqxz ETAOIN,.;:\n 0123456789",
                      np.uint8)
_CSV = np.frombuffer(b"0123456789,.-\n", np.uint8)
_PNG_MAGIC = np.frombuffer(b"\x89PNG\r\n\x1a\n", np.uint8)
_ELF_MAGIC = np.frombuffer(b"\x7fELF\x02\x01\x01\x00", np.uint8)
_X86 = np.frombuffer(b"\x55\x48\x89\xe5\x48\x83\xec\x00\xc3\x90\xe8\x0f"
                     b"\x44\x24\x8b\x45", np.uint8)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (more than 32 bits allowed)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


# ---------------------------------------------------------------- headers

@partial(jax.jit, static_argnames=("n_qps", "n_pkts"))
def rx_trace(key, n_qps: int, n_pkts: int):
    """One RX batch's arrival pattern: ``qpn`` uniform over the QPs in
    arrival order, ``rank`` the packet's position within its QP, ``cnt``
    the packets per QP.  Every batch reuses the pattern; batch ``b``'s
    PSNs are ``b * cnt[qpn] + rank``, so they continue across batches."""
    qpn = jax.random.randint(key, (n_pkts,), 0, n_qps, jnp.int32)
    order = jnp.argsort(qpn, stable=True)
    sq = qpn[order]
    first = jnp.searchsorted(sq, sq, side="left").astype(jnp.int32)
    rank = jnp.zeros(n_pkts, jnp.int32).at[order].set(
        jnp.arange(n_pkts, dtype=jnp.int32) - first)
    cnt = jnp.zeros(n_qps, jnp.int32).at[qpn].add(1)
    return qpn, rank, cnt


def batch_psn(b, qpn, rank, cnt):
    return (b * cnt[qpn] + rank) & PSN_MASK


@partial(jax.jit, static_argnames=("mtu",))
def rx_batch(b, qpn, rank, cnt, *, mtu: int):
    """Header columns of RX batch ``b``: full-MTU WRITE_ONLY packets,
    each addressing its own MTU slot of a 64 MiB region per QP."""
    n = qpn.shape[0]
    psn = batch_psn(b, qpn, rank, cnt)
    full = jnp.full(n, mtu, jnp.int32)
    return {"qpn": qpn, "opcode": jnp.full(n, WRITE_ONLY, jnp.int32),
            "psn": psn, "plen": full, "vaddr": (psn & 0x3FFF) * mtu,
            "dma_len": full, "ack_req": jnp.zeros(n, jnp.int32),
            "valid": jnp.ones(n, jnp.int32)}


# ------------------------------------------------------------ DPI payload

def _choice(key, table: np.ndarray, shape):
    return jnp.asarray(table)[jax.random.randint(key, shape, 0, len(table))]


def benign_beats(key, n: int) -> jax.Array:
    """(n, 64) uint8 beats of text, CSV or PNG-like payload."""
    kk, kt, kc, kp = jax.random.split(key, 4)
    kind = jax.random.randint(kk, (n, 1), 0, 3)
    text = _choice(kt, _TEXT, (n, BEAT))
    csv = _choice(kc, _CSV, (n, BEAT))
    png = jax.random.randint(kp, (n, BEAT), 0, 64).astype(jnp.uint8)
    png = png.at[:, :8].set(jnp.asarray(_PNG_MAGIC))
    return jnp.where(kind == 0, text, jnp.where(kind == 1, csv, png))


def malicious_beats(key, n: int) -> jax.Array:
    """(n, 64) uint8 executable-like beats: random bytes, 24 positions
    overwritten with x86-64 prologue opcodes, an ELF magic on 20%."""
    kb, ki, ko, kh = jax.random.split(key, 4)
    out = jax.random.randint(kb, (n, BEAT), 0, 256).astype(jnp.uint8)
    idx = jax.random.randint(ki, (n, 24), 0, BEAT)
    out = out.at[jnp.arange(n)[:, None], idx].set(_choice(ko, _X86, (n, 24)))
    hdr = jax.random.uniform(kh, (n, 1)) < 0.2
    pos = jnp.arange(BEAT)[None, :]
    elf = jnp.asarray(np.pad(_ELF_MAGIC, (0, BEAT - 8)))[None, :]
    return jnp.where(hdr & (pos < 8), elf, out)


def edge_beats(key, n: int, candidates: int, params, threshold, margin):
    """(n, 64) uint8 beats whose float32 reference DPI score lies near
    ``threshold``, as packed or partly embedded executables score: for
    each, ``candidates`` mixes of a benign and an executable beat (each
    byte from the executable one with a probability drawn per mix), and
    of those the one that scores nearest a target drawn ``margin[0]`` to
    ``margin[1]`` above or below the threshold."""
    kb, km, kp, kmix, kt, ks = jax.random.split(key, 6)
    c = n * candidates
    p = jax.random.uniform(kp, (c, 1))
    mix = jnp.where(jax.random.uniform(kmix, (c, BEAT)) < p,
                    malicious_beats(km, c), benign_beats(kb, c))
    s = reference.dpi_scores(mix, params)[:, 0].reshape(n, candidates)
    off = jax.random.uniform(kt, (n,), minval=margin[0], maxval=margin[1])
    sign = jnp.where(jax.random.bernoulli(ks, 0.5, (n,)), 1.0, -1.0)
    pick = jnp.argmin(jnp.abs(s - (threshold + sign * off)[:, None]), axis=1)
    return mix.reshape(n, candidates, BEAT)[jnp.arange(n), pick]


@partial(jax.jit, static_argnames=("n_pkts", "mtu", "n_edge", "candidates"))
def dpi_packets(key, n_pkts: int, mtu: int, pkt_share, beat_share,
                edge=None, *, n_edge: int = 0, candidates: int = 0):
    """(n_pkts, mtu) uint8 plaintext: benign packets, of which a share
    ``pkt_share`` carries malware in ``beat_share`` of its beats, and
    ``n_edge`` others one near-threshold beat each (``edge_beats``;
    ``edge`` is the DPI model's ``(params, threshold, margin)``)."""
    beats = mtu // BEAT
    kb, km, kp, kpos, ke = jax.random.split(key, 5)
    x = benign_beats(kb, n_pkts * beats).reshape(n_pkts, beats, BEAT)
    m = malicious_beats(km, n_pkts * beats).reshape(n_pkts, beats, BEAT)
    bad_pkt = jax.random.uniform(kp, (n_pkts, 1)) < pkt_share
    n_mal = jnp.round(beat_share * beats).astype(jnp.int32)
    pos_rank = jnp.argsort(jnp.argsort(
        jax.random.uniform(kpos, (n_pkts, beats)), axis=1), axis=1)
    if n_edge:
        kw, kat, kc = jax.random.split(ke, 3)
        which = jax.random.permutation(kw, n_pkts)[:n_edge]
        bad_pkt = bad_pkt.at[which].set(False)
        x = x.at[which, jax.random.randint(kat, (n_edge,), 0, beats)].set(
            edge_beats(kc, n_edge, candidates, *edge))
    bad_beat = bad_pkt & (pos_rank < n_mal)
    return jnp.where(bad_beat[..., None], m, x).reshape(n_pkts, mtu)


@partial(jax.jit, static_argnames=("n_per_class",))
def dpi_dataset(key, n_per_class: int):
    """Labelled training beats: benign (0) then malicious (1), shuffled."""
    kb, km, kperm = jax.random.split(key, 3)
    x = jnp.concatenate([benign_beats(kb, n_per_class),
                         malicious_beats(km, n_per_class)])
    y = jnp.concatenate([jnp.zeros(n_per_class), jnp.ones(n_per_class)])
    perm = jax.random.permutation(kperm, 2 * n_per_class)
    return x[perm], y[perm].astype(jnp.float32)


# ---------------------------------------------------------------- Criteo

@partial(jax.jit, static_argnames=("n_records", "n_dense", "n_sparse"))
def criteo_records(key, n_records: int, n_dense: int, n_sparse: int,
                   click_share=0.0):
    """Raw int32 records of ``n_dense + n_sparse + 1`` words: dense
    counts in [-100, 100000) (need Neg2Zero and Log), categorical ids in
    [0, 2**30) (need Modulus), then the click label, 1 with probability
    ``click_share``."""
    kd, ks, kl = jax.random.split(key, 3)
    dense = jax.random.randint(kd, (n_records, n_dense), -100, 100_000,
                               jnp.int32)
    sparse = jax.random.randint(ks, (n_records, n_sparse), 0, 1 << 30,
                                jnp.int32)
    label = jax.random.bernoulli(kl, click_share, (n_records, 1))
    return jnp.concatenate([dense, sparse, label.astype(jnp.int32)], axis=1)


def records_per_packet(mtu: int, rec_words: int) -> int:
    return (mtu // 4) // rec_words


def encode_packets(recs: jax.Array, mtu: int) -> jax.Array:
    """(n, w) int32 records -> (n_pkts, mtu) uint8: whole records per
    packet, zero-padded to the packet (and the last packet) end."""
    n, w = recs.shape
    rpp = records_per_packet(mtu, w)
    n_pkts = -(-n // rpp)
    x = jnp.pad(recs, ((0, n_pkts * rpp - n), (0, 0)))
    x = jnp.pad(x.reshape(n_pkts, rpp * w), ((0, 0), (0, mtu // 4 - rpp * w)))
    return jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(n_pkts, mtu)


def decode_packets(pkts: jax.Array, rec_words: int) -> jax.Array:
    """Inverse of ``encode_packets``: every record slot, padding included."""
    n_pkts, mtu = pkts.shape
    rpp = records_per_packet(mtu, rec_words)
    w = jax.lax.bitcast_convert_type(pkts.reshape(n_pkts, mtu // 4, 4),
                                     jnp.int32)
    return w[:, :rpp * rec_words].reshape(n_pkts * rpp, rec_words)
