"""End-to-end system tests: the BALBOA ingest path feeding real training
(the paper's §8 flow), storage straggler -> replica failover, and the
DLRM model behind it."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.dlrm import smoke_config
from repro.core.ingest import BalboaIngest, IngestConfig
from repro.core.services import PreprocService, ServiceChain
from repro.data import synthetic as syn
from repro.models.dlrm import DLRM

# vocabulary of the LM token shards shipped as ingest payload
VOCAB = 512


# ---------------------------------------------------------------------------
# Ingest: storage -> RDMA -> services -> device
# ---------------------------------------------------------------------------

def test_ingest_lm_shards_roundtrip():
    shard_fn = lambda i: syn.encode_lm_shard(
        syn.lm_shard(i, 4, 32, VOCAB))
    ing = BalboaIngest(IngestConfig(batch_bytes=1 << 16), None,
                       shard_fn, syn.decode_lm_shard)
    got = ing.fetch_shard(3)
    want = syn.lm_shard(3, 4, 32, VOCAB)
    np.testing.assert_array_equal(np.asarray(got["tokens"]), want["tokens"])
    np.testing.assert_array_equal(np.asarray(got["targets"]), want["targets"])


def test_ingest_straggler_failover():
    """First storage node never answers (dead peer): the QP timeout
    trips and the replica serves the shard."""
    shard_fn = lambda i: syn.encode_lm_shard(
        syn.lm_shard(i, 2, 16, VOCAB))
    ing = BalboaIngest(
        IngestConfig(batch_bytes=1 << 14, n_storage_nodes=2,
                     straggler_timeout_ticks=300), None,
        shard_fn, syn.decode_lm_shard)
    # kill node for shard 0's primary: drop all its outbound packets
    primary = ing.storage[0].node
    for (src, dst), link in ing.net.links.items():
        if src == primary.node_id:
            link.cfg.loss_prob = 1.0
    got = ing.fetch_shard(0)
    want = syn.lm_shard(0, 2, 16, VOCAB)
    np.testing.assert_array_equal(np.asarray(got["tokens"]), want["tokens"])
    assert ing.refetches >= 1


def test_ingest_failover_after_retry_exhaustion():
    """Killed storage node, deterministic: the trainer's retry budget
    exhausts (QP error) within the straggler window, the replica serves
    the shard via reestablish_qp, and the error state is cleared."""
    shard_fn = lambda i: syn.encode_lm_shard(
        syn.lm_shard(i, 2, 16, VOCAB))
    ing = BalboaIngest(
        IngestConfig(batch_bytes=1 << 14, n_storage_nodes=2,
                     straggler_timeout_ticks=400), None,
        shard_fn, syn.decode_lm_shard)
    # tight retry budget so exhaustion fits inside one straggler window
    ing.trainer.retx.MAX_RETRIES = 2
    ing.trainer.retx.timeout = 20
    primary = ing.storage[0].node
    for (src, dst), link in ing.net.links.items():
        if src == primary.node_id:          # kill ALL outbound traffic
            link.cfg.loss_prob = 1.0
    got = ing.fetch_shard(0)
    want = syn.lm_shard(0, 2, 16, VOCAB)
    np.testing.assert_array_equal(np.asarray(got["tokens"]), want["tokens"])
    assert ing.refetches >= 1
    # the dead QP genuinely exhausted its budget and was surfaced...
    assert ing.trainer.retx.exhausted
    qpn_dead = ing.trainer.retx.exhausted[0][0]
    # ...then cleared by the reestablish during failover
    assert not ing.trainer.qp_error(qpn_dead)
    assert ing.trainer.retx.outstanding(qpn_dead) == 0


def test_ingest_preprocessed_dlrm_stream():
    """Paper §8 end to end: raw records stream through the on-path
    preprocessing service and arrive device-ready."""
    n_dense, n_sparse, modulus = 13, 26, 1000
    mtu_records = (4096 // 4) // (n_dense + n_sparse)
    n_rec = mtu_records * 4       # 4 full packets
    shard_fn = lambda i: syn.encode_dlrm_shard(
        syn.dlrm_shard(i, n_rec, n_dense, n_sparse))
    # NOTE: header words travel in packet 0 — the service must not mangle
    # them; PreprocService only rewrites whole records, and we align the
    # payload so the 3-word header occupies the first record slot.
    raw = syn.dlrm_shard(7, n_rec, n_dense, n_sparse)
    svc = PreprocService(n_dense=n_dense, n_sparse=n_sparse, modulus=modulus)
    chain = ServiceChain(on_path=[svc])
    # feed the records directly (unit of the ingest transform)
    pay = np.zeros((4, 4096), np.uint8)
    rec_bytes = (n_dense + n_sparse) * 4
    per_pkt = mtu_records
    for p in range(4):
        chunk = raw[p * per_pkt:(p + 1) * per_pkt]
        pay[p, :per_pkt * rec_bytes] = chunk.view(np.uint8).reshape(-1)
    out, _ = chain.process(jnp.asarray(pay),
                           jnp.asarray(np.full(4, 4096, np.int32)))
    out = np.asarray(out)
    recs = np.concatenate([
        out[p, :per_pkt * rec_bytes].view(np.int32).reshape(per_pkt, -1)
        for p in range(4)])
    dense = recs[:, :n_dense].view(np.float32)
    np.testing.assert_allclose(
        dense, np.log1p(np.maximum(raw[:, :n_dense], 0)), rtol=1e-6)
    np.testing.assert_array_equal(recs[:, n_dense:],
                                  raw[:, n_dense:] % modulus)


# ---------------------------------------------------------------------------
# DLRM end to end (paper §8 model behind preprocessed features)
# ---------------------------------------------------------------------------

def test_dlrm_trains():
    cfg = smoke_config()
    model = DLRM(cfg)
    params = model.init_params(jax.random.key(0))
    raw = syn.dlrm_shard(0, 512, cfg.n_dense, cfg.n_sparse)
    labels = syn.dlrm_labels(raw, cfg.n_dense, cfg.modulus)
    dense = np.log1p(np.maximum(raw[:, :cfg.n_dense], 0)).astype(np.float32)
    sparse = (raw[:, cfg.n_dense:] % cfg.modulus).astype(np.int32)
    batch = {"dense": jnp.asarray(dense), "sparse": jnp.asarray(sparse),
             "label": jnp.asarray(labels)}

    # heavy-ball momentum: plain constant-step GD oscillates around the
    # optimum on this full-batch problem instead of settling
    vel = jax.tree.map(jnp.zeros_like, params)

    @jax.jit
    def step(p, v):
        (l, m), g = jax.value_and_grad(model.loss, has_aux=True)(p, batch)
        v = jax.tree.map(lambda vv, gg: 0.9 * vv + gg, v, g)
        p = jax.tree.map(lambda a, b: a - 0.005 * b, p, v)
        return p, v, l, m["acc"]

    accs = []
    for _ in range(200):
        params, vel, loss, acc = step(params, vel)
        accs.append(float(acc))
    assert accs[-1] > 0.8, f"DLRM failed to learn: acc={accs[-1]}"


def test_dlrm_init_is_pinned():
    """The smoke DLRM's initial parameters, byte for byte: every leaf in
    ``jax.tree.leaves`` order, hashed."""
    params = DLRM(smoke_config()).init_params(jax.random.key(0))
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == (
        "0f5b445a3f30f3e68956462642a851b5a4b0050128d709fcd2c5a4c1e3ff6505")
