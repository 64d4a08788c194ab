"""The paper's §8 use case, end to end: DLRM online training where every
batch STREAMS from disaggregated storage over BALBOA RDMA — striped
across all replicas on concurrent QPs, preprocessed tile-by-tile ON THE
DATAPATH the moment bytes are acknowledged (Neg2Zero -> Log, Modulus),
and landed directly in pre-sharded device buffers.  The CPU never
touches a feature byte: ``decode_fn`` is poisoned to prove it.

  PYTHONPATH=src python examples/dlrm_ingest.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.dlrm import smoke_config
from repro.core.ingest import (BalboaIngest, IngestConfig,
                               make_dlrm_tile_decoder)
from repro.data import synthetic as syn
from repro.models.dlrm import DLRM


def main():
    enable_compile_cache()
    cfg = smoke_config()
    rec_w = cfg.n_dense + cfg.n_sparse
    recs_per_pkt = (4096 // 4) // rec_w
    n_pkts = 8                        # packets per shard
    n_rec = recs_per_pkt * n_pkts

    # --- storage shards: RAW records (negative dense, unbounded sparse)
    # in the record-aligned packet layout the stripes preserve
    def shard_fn(i):
        return syn.encode_dlrm_packets(
            syn.dlrm_shard(i, n_rec, cfg.n_dense, cfg.n_sparse))

    def poisoned_decode(raw):
        raise AssertionError("host decode touched payload bytes")

    # Streaming ingest: 2 replicas x 2 QPs, 2-packet fragment tiles.
    # Preprocessing runs per tile (the fused Pallas kernel) as each
    # tile's bytes are acknowledged — process-as-it-arrives.
    ing = BalboaIngest(
        IngestConfig(batch_bytes=n_pkts * 4096, n_storage_nodes=2,
                     qps_per_node=2, tile_pkts=2,
                     link_bw_pkts_per_tick=1),
        None, shard_fn, decode_fn=poisoned_decode,
        tile_to_batch=make_dlrm_tile_decoder(cfg.n_dense, cfg.n_sparse,
                                             cfg.modulus))

    model = DLRM(cfg)
    params = model.init_params(jax.random.key(0))

    @jax.jit
    def train_step(p, batch):
        (l, m), g = jax.value_and_grad(model.loss, has_aux=True)(p, batch)
        p = jax.tree.map(lambda a, b: a - 0.05 * b, p, g)
        return p, l, m["acc"]

    t0 = time.time()
    losses, goodputs, overlaps = [], [], []
    for i, (dev_batch, rep) in enumerate(ing.stream_batches(30)):
        goodputs.append(rep.goodput_bytes_per_tick)
        overlaps.append(rep.overlap_efficiency)
        # labels are control-plane metadata (derived from the synthetic
        # rule), not payload bytes
        raw = syn.dlrm_shard(i, n_rec, cfg.n_dense, cfg.n_sparse)
        labels = syn.dlrm_labels(raw, cfg.n_dense, cfg.modulus)
        batch = {"dense": dev_batch["dense"],
                 "sparse": dev_batch["sparse"],
                 "label": jnp.asarray(labels)}
        # sanity: tile-granular on-arrival preprocessing == reference
        want = np.log1p(np.maximum(raw[:, :cfg.n_dense], 0))
        np.testing.assert_allclose(np.asarray(batch["dense"]), want,
                                   rtol=1e-5)
        for _ in range(5):         # a few optimizer steps per shard
            params, loss, acc = train_step(params, batch)
        losses.append(float(loss))
        if i % 10 == 0:
            print(f"[dlrm] shard {i}: loss {float(loss):.4f} "
                  f"acc {float(acc):.3f} "
                  f"goodput {rep.goodput_bytes_per_tick:.0f} B/tick "
                  f"overlap {rep.overlap_efficiency:.2f}")
    dt = time.time() - t0
    print(f"[dlrm] 30 shards ({30*n_rec} records) in {dt:.1f}s; "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}; "
          f"mean goodput {np.mean(goodputs):.0f} B/tick, "
          f"mean overlap {np.mean(overlaps):.2f}; "
          f"host payload bytes copied: {ing.host_payload_bytes}")
    assert losses[-1] < losses[0]
    assert ing.host_payload_bytes == 0
    print("dlrm_ingest OK")


if __name__ == "__main__":
    main()
