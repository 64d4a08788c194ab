"""Service-enhanced RDMA flow (paper §5 end to end): the sender encrypts
on its TX path, the receiver decrypts on-path and runs ML-DPI on the
parallel path; the traffic sniffer (paper §4.7) captures the ciphertext
wire traffic into a PCAP you can open in Wireshark.

  PYTHONPATH=src python examples/secure_flow.py
"""
import os
import tempfile

import numpy as np
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core.netsim import LinkConfig, Network
from repro.core.rdma import RdmaNode, run_network
from repro.core.services import AesService, DpiService, ServiceChain
from repro.core.sniffer import TrafficSniffer
from repro.data.dpi_dataset import make_dataset, payload_with_embedded_malware
from repro.kernels.dpi_mlp import train_dpi_params

KEY = np.arange(16, dtype=np.uint8)


def main():
    enable_compile_cache()
    # train the DPI model (paper: CSV/PNG/TXT vs executables)
    x, y = make_dataset(2048, seed=0)
    dpi_params = train_dpi_params(x, y, steps=200)

    rng = np.random.default_rng(0)
    benign = payload_with_embedded_malware(65536, 0.0, rng)  # text/CSV/PNG
    evil = payload_with_embedded_malware(65536, 0.2, rng)    # 20% malware

    net = Network(2, LinkConfig(loss_prob=0.02, latency_ticks=3, seed=1))
    sniffer = TrafficSniffer(capture_payload=True)
    # DPI must inspect the *decrypted* stream -> parallel_after placement
    recv_chain = ServiceChain(
        on_path=[AesService(key=KEY, decrypt=True)],
        parallel_after=[DpiService(params=dpi_params)])
    a = RdmaNode(0, net, sniffer=sniffer)
    b = RdmaNode(1, net, services=recv_chain)
    qpn_a, _, _ = a.init_rdma(1 << 18, b)

    enc = AesService(key=KEY)
    for name, data in (("benign", benign), ("malicious", evil)):
        ct = np.asarray(enc(jnp.asarray(data.reshape(-1, 4096)),
                            jnp.asarray(np.full(len(data) // 4096, 4096,
                                                np.int32))))
        flagged_before = b.stats.dpi_flagged
        a.rdma_write(qpn_a, ct.reshape(-1))
        run_network([a, b], max_ticks=50_000)
        got = b._qp_buffer[1][1][:len(data)]
        ok = (got == data).all()
        flags = b.stats.dpi_flagged - flagged_before
        print(f"[secure] {name:10s} delivered={ok} "
              f"dpi_flagged_packets={flags}/{len(data)//4096}")
        assert ok
    assert b.stats.dpi_flagged > 0, "DPI missed the malicious flow"

    pcap = os.path.join(tempfile.gettempdir(), "balboa_flow.pcap")
    n = sniffer.write_pcap(pcap)
    print(f"[secure] wrote {n} packets to {pcap} "
          f"(RoCE v2 BTH frames; wire payloads are AES ciphertext)")
    print("secure_flow OK")


if __name__ == "__main__":
    main()
