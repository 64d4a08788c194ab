"""Service-chain tests (paper §5): on-path AES transform, parallel-path
DPI decisions, DLRM preprocessing, and chain composition — plus the
end-to-end property that an encrypt-side + decrypt-side pair of BALBOA
nodes is transparent to the application."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.services import (AesService, CrcService, DpiService,
                                 PreprocService, ServiceChain)
from repro.core.netsim import LinkConfig, Network
from repro.core.rdma import RdmaNode, run_network
from repro.data.dpi_dataset import make_dataset, payload_with_embedded_malware
from repro.kernels.dpi_mlp import train_dpi_params

KEY = np.arange(16, dtype=np.uint8)


def test_aes_service_roundtrip():
    enc = AesService(key=KEY)
    dec = AesService(key=KEY, decrypt=True)
    pay = np.random.default_rng(0).integers(0, 256, (8, 4096), dtype=np.uint8)
    plen = np.full(8, 4096, np.int32)
    ct = np.asarray(enc(jnp.asarray(pay), jnp.asarray(plen)))
    assert not (ct == pay).all()
    pt = np.asarray(dec(jnp.asarray(ct), jnp.asarray(plen)))
    np.testing.assert_array_equal(pt, pay)


def test_preproc_service_transforms_records():
    svc = PreprocService(n_dense=13, n_sparse=26, modulus=1000)
    rec_words = 39
    n_rec = 4096 // 4 // rec_words
    recs = np.random.default_rng(1).integers(
        -50, 10**6, (2, n_rec * rec_words), dtype=np.int32)
    pay = np.zeros((2, 4096), np.uint8)
    pay[:, :n_rec * rec_words * 4] = recs.view(np.uint8)
    out = np.asarray(svc(jnp.asarray(pay), jnp.asarray([4096, 4096],
                                                       np.int32)))
    out_words = out[:, :n_rec * rec_words * 4].view(np.int32).reshape(
        2, n_rec, rec_words)
    want_dense = np.log1p(np.maximum(
        recs.reshape(2, n_rec, rec_words)[:, :, :13], 0).astype(np.float32))
    np.testing.assert_allclose(out_words[:, :, :13].view(np.float32),
                               want_dense, rtol=1e-6)
    np.testing.assert_array_equal(
        out_words[:, :, 13:], recs.reshape(2, n_rec, rec_words)[:, :, 13:]
        % 1000)


@pytest.fixture(scope="module")
def dpi_params():
    x, y = make_dataset(2048, seed=0)
    return train_dpi_params(x, y, steps=250)


def test_dpi_service_flags_malware(dpi_params):
    svc = DpiService(params=dpi_params)
    rng = np.random.default_rng(2)
    mal = np.stack([payload_with_embedded_malware(4096, 1.0, rng)
                    for _ in range(16)])
    ben = np.stack([payload_with_embedded_malware(4096, 0.0, rng)
                    for _ in range(16)])
    plen = np.full(16, 4096, np.int32)
    f_mal = np.asarray(svc(jnp.asarray(mal), jnp.asarray(plen)))
    f_ben = np.asarray(svc(jnp.asarray(ben), jnp.asarray(plen)))
    assert f_mal.mean() > 0.9, f"missed malware: {f_mal.mean()}"
    assert f_ben.mean() < 0.2, f"false positives: {f_ben.mean()}"


def test_service_chain_order_and_flags(dpi_params):
    """Parallel-path services see the pre-transform stream; on-path
    services compose in order."""
    enc = AesService(key=KEY)
    dpi = DpiService(params=dpi_params)
    chain = ServiceChain(on_path=[enc], parallel=[dpi])
    rng = np.random.default_rng(3)
    pay = np.stack([payload_with_embedded_malware(4096, 1.0, rng)
                    for _ in range(4)])
    plen = np.full(4, 4096, np.int32)
    out, flags = chain.process(jnp.asarray(pay), jnp.asarray(plen))
    # DPI inspected the *plaintext* copy -> flags fire even though the
    # on-path output is ciphertext
    assert np.asarray(flags).all()
    assert not (np.asarray(out) == pay).all()


def test_e2e_encrypted_rdma_flow(dpi_params):
    """Sender encrypts on its TX service chain; receiver decrypts on RX:
    the application sees plaintext, the wire sees ciphertext."""
    rng = np.random.default_rng(4)
    data = rng.integers(0, 256, 65536, dtype=np.uint8)
    net = Network(2, LinkConfig(latency_ticks=2, seed=5))
    # receiver runs decrypt on-path + DPI parallel-path
    recv_chain = ServiceChain(on_path=[AesService(key=KEY, decrypt=True)],
                              parallel=[DpiService(params=dpi_params)])
    a = RdmaNode(0, net)
    b = RdmaNode(1, net, services=recv_chain)
    qpn_a, _, _ = a.init_rdma(1 << 18, b)
    # encrypt before send (TX-side on-path service)
    enc = AesService(key=KEY)
    ct = np.asarray(enc(jnp.asarray(data.reshape(16, 4096)),
                        jnp.asarray(np.full(16, 4096, np.int32))))
    a.rdma_write(qpn_a, ct.reshape(-1))
    run_network([a, b], max_ticks=20_000)
    np.testing.assert_array_equal(b._qp_buffer[1][1][:len(data)], data)


def test_service_chain_flag_bit_layout():
    """Decision-flag bits have named positions exposed on the chain
    (pre-transform taps first, then post-transform taps) — consumers
    address flags by name, never by inspector insertion order."""
    from repro.core.services import ParallelPathService

    class _Always(ParallelPathService):
        def __init__(self, name):
            self.name = name

        def __call__(self, payload, plen):
            return jnp.ones(payload.shape[0], jnp.int32)

    class _Never(ParallelPathService):
        def __init__(self, name):
            self.name = name

        def __call__(self, payload, plen):
            return jnp.zeros(payload.shape[0], jnp.int32)

    chain = ServiceChain(parallel=[_Always("icrc"), _Never("rate-limit")],
                         parallel_after=[_Always("ml-dpi")])
    assert chain.flag_bits == {"icrc": 0, "rate-limit": 1, "ml-dpi": 2}
    pay = np.zeros((3, 256), np.uint8)
    _, flags = chain.process(jnp.asarray(pay),
                             jnp.asarray(np.full(3, 256, np.int32)))
    flags = np.asarray(flags)
    assert ((flags >> chain.flag_bits["icrc"]) & 1).all()
    assert not ((flags >> chain.flag_bits["rate-limit"]) & 1).any()
    assert ((flags >> chain.flag_bits["ml-dpi"]) & 1).all()
    # duplicate names get disambiguated, never silently merged
    dup = ServiceChain(parallel=[_Always("icrc"), _Never("icrc")])
    assert sorted(dup.flag_bits.values()) == [0, 1]
    # the SAME instance tapping both placements gets two distinct bits
    tap = _Always("ml-dpi")
    both = ServiceChain(parallel=[tap], parallel_after=[tap])
    assert sorted(both.flag_bits.values()) == [0, 1]
    _, f2 = both.process(jnp.asarray(pay),
                         jnp.asarray(np.full(3, 256, np.int32)))
    assert (np.asarray(f2) == 0b11).all()
    # the 32-bit host-directed command bounds the inspector count
    with pytest.raises(ValueError):
        ServiceChain(parallel=[_Never(f"i{i}") for i in range(33)])


def test_crc_service_flags_corruption():
    svc = CrcService()
    pay = np.random.default_rng(6).integers(0, 256, (4, 512), dtype=np.uint8)
    flags = np.asarray(svc(jnp.asarray(pay),
                           jnp.asarray(np.full(4, 512, np.int32))))
    assert flags.shape == (4,)          # (integrity values, smoke only)


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("svc", ["aes-enc", "aes-dec", "dpi", "preproc",
                                 "crc"])
def test_service_rows_independent_of_padding(svc, use_pallas, dpi_params):
    """RdmaNode runs the chain on its RX batch padded with zero rows and
    keeps the first N rows: every service's rows must not see the pad."""
    make = {"aes-enc": lambda: AesService(key=KEY),
            "aes-dec": lambda: AesService(key=KEY, decrypt=True),
            "dpi": lambda: DpiService(params=dpi_params),
            "preproc": lambda: PreprocService(modulus=1000),
            "crc": lambda: CrcService()}[svc]
    service = make()
    service.use_pallas = use_pallas
    n, n_pad, mtu = 5, 16, 512
    rng = np.random.default_rng(7)
    pay = rng.integers(0, 256, (n, mtu), dtype=np.uint8)
    plen = rng.integers(1, mtu + 1, n).astype(np.int32)
    padded = np.zeros((n_pad, mtu), np.uint8)
    padded[:n] = pay
    plen_p = np.zeros(n_pad, np.int32)
    plen_p[:n] = plen
    alone = np.asarray(service(jnp.asarray(pay), jnp.asarray(plen)))
    in_pad = np.asarray(service(jnp.asarray(padded), jnp.asarray(plen_p)))
    np.testing.assert_array_equal(in_pad[:n], alone)
