"""DLRM — the paper's own §8 workload: deep learning recommendation
model (bottom MLP over dense features, embedding tables for sparse
features, pairwise dot interaction, top MLP) trained online behind the
BALBOA ingest path."""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.dlrm import DLRMConfig


class Spec(NamedTuple):
    """One parameter: its shape and initializer."""
    shape: Tuple[int, ...]
    init: str = "fan_in"      # fan_in (2-D weights) | normal | zeros


def _init_one(spec: Spec, key, param_dtype: str):
    dtype = jnp.dtype(param_dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "normal":
        std = 0.02
    elif spec.init == "fan_in":
        std = 1.0 / math.sqrt(spec.shape[0])
    else:
        raise ValueError(f"unknown init {spec.init}")
    return (std * jax.random.normal(key, spec.shape, jnp.float32)
            ).astype(dtype)


def init(tree, key, param_dtype: str):
    """Materialize a ``Spec`` tree: one key per leaf, in leaf order."""
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, Spec))
    keys = jax.random.split(key, len(leaves))
    vals = [_init_one(s, k, param_dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


class DLRM:
    def __init__(self, cfg: DLRMConfig):
        self.cfg = cfg

    def param_spec(self):
        cfg = self.cfg
        spec: Dict = {"tables": {}}
        for i in range(cfg.n_sparse):
            spec["tables"][f"t{i}"] = Spec(
                (cfg.embed_rows, cfg.embed_dim), "normal")
        dims = (cfg.n_dense,) + cfg.bottom_mlp
        spec["bottom"] = {
            f"l{i}": {"w": Spec((dims[i], dims[i + 1])),
                      "b": Spec((dims[i + 1],), "zeros")}
            for i in range(len(dims) - 1)}
        n_f = cfg.n_sparse + 1
        inter_dim = cfg.bottom_mlp[-1] + n_f * (n_f - 1) // 2
        tdims = (inter_dim,) + cfg.top_mlp
        spec["top"] = {
            f"l{i}": {"w": Spec((tdims[i], tdims[i + 1])),
                      "b": Spec((tdims[i + 1],), "zeros")}
            for i in range(len(tdims) - 1)}
        return spec

    def init_params(self, key):
        return init(self.param_spec(), key, self.cfg.param_dtype)

    def forward(self, params, dense: jax.Array, sparse: jax.Array
                ) -> jax.Array:
        """dense (B, n_dense) float32 (already preprocessed on-path!),
        sparse (B, n_sparse) int32 in [0, embed_rows)."""
        cfg = self.cfg
        x = dense
        for i in range(len(cfg.bottom_mlp)):
            l = params["bottom"][f"l{i}"]
            x = x @ l["w"] + l["b"]
            x = jax.nn.relu(x)
        embs = [params["tables"][f"t{i}"][sparse[:, i]]
                for i in range(cfg.n_sparse)]
        feats = jnp.stack([x] + embs, axis=1)       # (B, F, D)
        inter = jnp.einsum("bfd,bgd->bfg", feats, feats)
        iu = jnp.triu_indices(feats.shape[1], k=1)
        z = jnp.concatenate([x, inter[:, iu[0], iu[1]]], axis=1)
        for i in range(len(cfg.top_mlp)):
            l = params["top"][f"l{i}"]
            z = z @ l["w"] + l["b"]
            if i < len(cfg.top_mlp) - 1:
                z = jax.nn.relu(z)
        return z[:, 0]

    def loss(self, params, batch) -> Tuple[jax.Array, Dict]:
        logits = self.forward(params, batch["dense"], batch["sparse"])
        y = batch["label"]
        nll = jnp.mean(jnp.maximum(logits, 0) - logits * y
                       + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        acc = jnp.mean((logits > 0) == (y > 0.5))
        return nll, {"loss": nll, "acc": acc}
