"""A configuration, deployed on the program: its service chain, the
payload its traffic carries, and what the reference says that payload
must become.

A configuration file names its services (``aes_ecb_decrypt`` on the
path, ``ml_dpi`` beside it after decryption, ``dlrm_preproc`` on the
path) and its payload (``dpi_mix``: AES-encrypted text, CSV and PNG with
a share of embedded malware and a share of near-threshold content;
``criteo``: record-aligned Criteo rows, click label included).
The AES key and the DPI model are fixed by the configuration, not by the
run's seed: a deployment's key and model do not change from one run to
the next, and they are constants of the compiled chain.
"""
from __future__ import annotations

import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, harness, reference


def aes_round_keys(cfg):
    key = np.frombuffer(bytes.fromhex(cfg["aes_key_hex"]), np.uint8)
    return key, jnp.asarray(reference.expand_key(key))


def dpi_params(cfg):
    d = cfg["dpi"]
    key = jax.random.key(d["model_seed"])
    t = time.perf_counter()
    x, y = gen.dpi_dataset(jax.random.fold_in(key, 0),
                           d["train_beats_per_class"])
    params = jax.block_until_ready(reference.train_dpi(
        x, y, jax.random.fold_in(key, 1), steps=d["train_steps"],
        lr=d["lr"]))
    harness.log(f"DPI model trained in {time.perf_counter() - t:.2f} s")
    return params


class Deployment:
    """The program's chain for ``cfg`` and the reference beside it."""

    def __init__(self, cfg):
        from repro.core.services import (AesService, DpiService,
                                         PreprocService, ServiceChain)
        self.cfg = cfg
        self.mtu = cfg["transport"]["mtu"]
        svc = cfg["services"]
        on_path, after = [], []
        self.params = None
        if "aes_ecb_decrypt" in svc["on_path"]:
            self.key, self.rk = aes_round_keys(cfg)
            on_path.append(AesService(key=self.key, decrypt=True))
        if "dlrm_preproc" in svc["on_path"]:
            r = cfg["records"]
            # the click label, after the categorical ids, rides as one
            # more Modulus column: 0 and 1 come out as they went in
            on_path.append(PreprocService(n_dense=r["n_dense"],
                                          n_sparse=r["n_sparse"] + 1,
                                          modulus=r["modulus"]))
        if "ml_dpi" in svc.get("parallel_after", []):
            self.params = dpi_params(cfg)
            self.threshold = cfg["dpi"]["threshold"]
            # the MXU sums in another order than the reference: a flag is
            # compared only where the reference score lies this far or
            # further from the threshold
            self.band = cfg["dpi"]["decision_band"]
            after.append(DpiService(params=self.params,
                                    threshold=self.threshold))
        self.chain = ServiceChain(on_path=on_path, parallel_after=after)
        self.kernels = ([("aes_ecb", "bytes")] if "aes_ecb_decrypt" in
                        svc["on_path"] else []) + \
            ([("dpi_mlp", "pkts")] if self.params is not None else []) + \
            ([("preproc", "records")] if "dlrm_preproc" in svc["on_path"]
             else [])

    # ---------------------------------------------------------- traffic
    @property
    def rec_words(self):
        """Words of a record: dense, categorical, the click label."""
        r = self.cfg["records"]
        return r["n_dense"] + r["n_sparse"] + 1

    def records(self, key, n_records: int):
        r = self.cfg["records"]
        return gen.criteo_records(key, n_records, r["n_dense"],
                                  r["n_sparse"], r["click_share"])

    def preproc(self, recs, mantissa_bits: int = 23):
        """The reference preprocessing of raw records."""
        r = self.cfg["records"]
        return reference.preproc(recs, n_dense=r["n_dense"],
                                 n_sparse=r["n_sparse"],
                                 modulus=r["modulus"],
                                 mantissa_bits=mantissa_bits)

    def packets(self, key, n_pkts: int):
        """(wire payload, plaintext payload) of ``n_pkts`` packets."""
        p = self.cfg["payload"]
        if p["kind"] == "dpi_mix":
            n_edge = round(p["edge_pkt_share"] * n_pkts)
            plain = gen.dpi_packets(
                key, n_pkts, self.mtu, p["malware_pkt_share"],
                p["malware_beat_share"],
                (self.params, self.threshold, tuple(p["edge_margin"])),
                n_edge=n_edge, candidates=p["edge_candidates"])
            return reference.encrypt_packets(plain, self.rk), plain
        if p["kind"] == "criteo":
            rpp = gen.records_per_packet(self.mtu, self.rec_words)
            pk = gen.encode_packets(self.records(key, n_pkts * rpp),
                                    self.mtu)
            return pk, pk
        raise ValueError(f"unknown payload kind {p['kind']!r}")

    # -------------------------------------------------------- reference
    def plain_of(self, wire):
        """The plaintext of wire payload rows (the reference decrypts)."""
        if "aes_ecb_decrypt" not in self.cfg["services"]["on_path"]:
            return wire
        n, mtu = wire.shape
        return reference.aes_decrypt(wire.reshape(-1, 16),
                                     self.rk).reshape(n, mtu)

    def expect(self, plain, plen):
        """What the chain must return for plaintext ``plain``: the
        payload, and per packet (flag, inside the DPI band) or None."""
        out, flags = plain, None
        if "dlrm_preproc" in self.cfg["services"]["on_path"]:
            recs = gen.decode_packets(plain, self.rec_words)
            out = gen.encode_packets(self.preproc(recs), self.mtu)
        if self.params is not None:
            s = reference.packet_scores(out, plen, self.params)
            flags = (s > self.threshold, jnp.abs(s - self.threshold)
                     < self.band)
        return out, flags

    def call_sizes(self, rows: int):
        """Per-kernel call arguments (for ``kernel_counts``) of one chain
        call over ``rows`` packets."""
        rpp = gen.records_per_packet(self.mtu, self.rec_words) \
            if self.cfg.get("records") else 0
        args = {"bytes": (rows * self.mtu,), "pkts": (rows, self.mtu),
                "records": (rows * rpp, self.rec_words if rpp else 0)}
        return {k: args[unit] for k, unit in self.kernels}

    # ---------------------------------------------------------- control
    def control_chain(self, kind: str):
        """The reference put in the chain's place, one step below the
        float32 the configuration states: ``bf16`` (each DPI dot's
        operands and log1p's input and result rounded to bfloat16; AES
        has no precision to lower and stays exact)."""
        if kind != "bf16":
            raise ValueError(f"unknown control {kind!r}")

        def process(payload, plen):
            out = self.plain_of(payload)
            if "dlrm_preproc" in self.cfg["services"]["on_path"]:
                recs = gen.decode_packets(out, self.rec_words)
                out = gen.encode_packets(self.preproc(recs, 7), self.mtu)
            flags = jnp.zeros(payload.shape[0], jnp.int32)
            if self.params is not None:
                s = reference.packet_scores(out, plen, self.params, 7)
                flags = (s > self.threshold).astype(jnp.int32)
            return out, flags
        return types.SimpleNamespace(process=jax.jit(process))
