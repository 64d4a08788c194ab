"""Mixture-of-Experts FFN (DeepSeek-V2/V3 style).

Design (EP x TP, dry-run-friendly static shapes):

  * Routing: softmax top-k (+ load-balance aux loss) for V2, or
    sigmoid + aux-loss-free gate bias for V3 [arXiv:2408.15664].
  * Dispatch: sort-based capacity buckets built *per row* (a row = up to
    4096 contiguous tokens of one sequence), so the argsort never crosses
    a data shard -> no collective inside dispatch.
  * Expert compute: experts sharded over the "data" mesh axis (EP), the
    per-expert hidden dim over "model" (TP).  The relayout from
    row-sharded dispatch buckets to expert-sharded buckets is expressed
    as a sharding constraint — GSPMD lowers it to the EP all-to-all.
  * The token stream is processed in chunks of 16 rows (one per data
    shard) under lax.scan, bounding the all-to-all transient to
    ~0.6 GB/device even for deepseek-v3-671b @ train_4k.
  * Tokens over capacity lose that expert (standard "dropping"); shared
    experts are a dense always-on FFN so no token is ever fully dropped.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.models.layers import ffn, ffn_spec
from repro.models.params import Spec
from repro.parallel.sharding import constrain

ROW_LEN = 4096          # tokens per dispatch row (<= one sequence)
ROWS_PER_CHUNK = 16     # rows processed per scan step (1 per data shard)
CAPACITY_FACTOR = 1.25
FLAT_PATH_MAX_TOKENS = 8192   # decode: gather-all dispatch below this


def _eax(cfg: ModelConfig) -> str:
    """Logical mesh axis for the expert dim (perf knob: 'ep2d' shards
    experts over (data x model) jointly -> no TP psum over the dispatched
    buffer, the dominant collective of the ep_tp baseline)."""
    return "expert2d" if cfg.expert_sharding == "ep2d" else "expert"


def moe_spec(cfg: ModelConfig):
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    eax = _eax(cfg)
    ffax = None if cfg.expert_sharding == "ep2d" else "expert_ff"
    spec = {
        "w_router": Spec((d, e), ("embed", None)),
        "w1": Spec((e, d, f), (eax, None, ffax)),
        "w3": Spec((e, d, f), (eax, None, ffax)),
        "w2": Spec((e, f, d), (eax, ffax, None)),
    }
    if cfg.aux_free_bias:
        spec["gate_bias"] = Spec((e,), (None,), "zeros", dtype="float32")
    if cfg.n_shared_experts:
        spec["shared"] = ffn_spec(d, cfg.n_shared_experts * f)
    return spec


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------

def route(cfg: ModelConfig, p, x: jax.Array):
    """x: (..., d) -> (ids (...,k), weights (...,k), aux_loss, load (E,))."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        p["w_router"].astype(jnp.float32))
    k, e = cfg.top_k, cfg.n_experts
    if cfg.gate_fn == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores
        if cfg.aux_free_bias:
            sel = scores + jax.lax.stop_gradient(
                p["gate_bias"].astype(jnp.float32))
        _, ids = jax.lax.top_k(sel, k)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
        w = w * cfg.routed_scaling
        probs = scores / jnp.maximum(
            jnp.sum(scores, axis=-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(probs, k)
        w = w * cfg.routed_scaling
    # load-balance statistics (flatten all token dims)
    flat_ids = ids.reshape(-1, k)
    load = jnp.zeros((e,), jnp.float32).at[flat_ids.reshape(-1)].add(1.0)
    load = load / jnp.maximum(jnp.sum(load), 1.0)
    aux = jnp.asarray(0.0, jnp.float32)
    if cfg.router_aux_coef:
        importance = jnp.mean(probs.reshape(-1, e), axis=0)
        aux = cfg.router_aux_coef * e * jnp.sum(load * importance)
    return ids, w.astype(x.dtype), aux, load


# ---------------------------------------------------------------------------
# Sort-based capacity dispatch (per row, no cross-shard ops)
# ---------------------------------------------------------------------------

def _dispatch_row(ids: jax.Array, w: jax.Array, n_tokens: int,
                  n_experts: int, capacity: int):
    """ids,w: (L, k) -> bucket token indices and weights (E, C).

    Sentinel index == L marks an empty slot (gathers a zero row)."""
    l, k = ids.shape
    flat_e = ids.reshape(-1)
    flat_w = w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(l, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_tok[order], flat_w[order]
    group_start = jnp.searchsorted(se, jnp.arange(n_experts), side="left")
    rank = jnp.arange(l * k, dtype=jnp.int32) - group_start[se].astype(jnp.int32)
    keep = rank < capacity
    slot = jnp.where(keep, rank, capacity)           # OOB -> dropped write
    buf_tok = jnp.full((n_experts, capacity), l, jnp.int32)
    buf_tok = buf_tok.at[se, slot].set(st, mode="drop")
    buf_w = jnp.zeros((n_experts, capacity), w.dtype)
    buf_w = buf_w.at[se, slot].set(sw, mode="drop")
    return buf_tok, buf_w


def _combine_row(buf_tok, buf_w, y_e, n_tokens: int):
    """Scatter-add expert outputs back to token order. y_e: (E, C, d)."""
    d = y_e.shape[-1]
    y = jnp.zeros((n_tokens + 1, d), y_e.dtype)
    y = y.at[buf_tok].add(y_e * buf_w[..., None])
    return y[:n_tokens]


def _expert_ffn(cfg: ModelConfig, p, x_e: jax.Array,
                compute_dtype) -> jax.Array:
    """x_e: (..., E, C, d) expert-sharded buckets -> same shape."""
    w1 = p["w1"].astype(compute_dtype)
    w3 = p["w3"].astype(compute_dtype)
    w2 = p["w2"].astype(compute_dtype)
    h1 = jnp.einsum("...ecd,edf->...ecf", x_e, w1)
    h3 = jnp.einsum("...ecd,edf->...ecf", x_e, w3)
    h = jax.nn.silu(h1) * h3
    eax = _eax(cfg)
    ffax = None if cfg.expert_sharding == "ep2d" else "expert_ff"
    if x_e.ndim == 4:
        h = constrain(h, None, eax, None, ffax)
    else:
        h = constrain(h, eax, None, ffax)
    y = jnp.einsum("...ecf,efd->...ecd", h, w2)
    # NOTE (§Perf, refuted hypothesis #3): constraining this output's d
    # over "model" to force a reduce-scatter instead of the all-reduce
    # made the collective term WORSE (369 -> 430 s) — GSPMD re-shards the
    # combine inputs instead.  The identified real fix is a shard_map MoE
    # inner loop that combines per-shard partials BEFORE one psum of the
    # (16x smaller) token tensor; see EXPERIMENTS.md.
    return y


def moe_ffn(cfg: ModelConfig, p, x: jax.Array, compute_dtype=jnp.bfloat16
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Routed + shared expert FFN.  x: (B, S, d).

    Returns (y, aux_loss, expert_load)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    n_tokens = b * s

    if n_tokens <= FLAT_PATH_MAX_TOKENS:
        y, aux, load = _moe_flat(cfg, p, x, compute_dtype)
    elif cfg.expert_sharding == "ep_sm":
        from repro.parallel.sharding import active_mesh
        if active_mesh() is not None:
            y, aux, load = _moe_chunked_shardmap(cfg, p, x, compute_dtype)
        else:  # no mesh context (smoke tests): pjit path
            y, aux, load = _moe_chunked(cfg, p, x, compute_dtype)
    else:
        y, aux, load = _moe_chunked(cfg, p, x, compute_dtype)

    if cfg.n_shared_experts:
        y = y + ffn(p["shared"], x, compute_dtype)
    return constrain(y, "batch", "seq", "d_model"), aux, load


def _moe_flat(cfg, p, x, compute_dtype):
    """Decode path: few tokens; gather-all, dispatch once, EP compute."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    n = b * s
    xf = x.reshape(n, d)
    ids, w, aux, load = route(cfg, p, xf)
    # small-N floor: with few tokens, hot experts easily exceed the
    # proportional capacity — give decode enough headroom to avoid drops.
    cap = max(math.ceil(CAPACITY_FACTOR * n * k / e), min(n, 16))
    buf_tok, buf_w = _dispatch_row(ids, w, n, e, cap)
    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    x_e = x_pad[buf_tok]                               # (E, C, d)
    eax = _eax(cfg)
    x_e = constrain(x_e, eax, None, None)              # EP all-to-all
    y_e = _expert_ffn(cfg, p, x_e, compute_dtype)
    y_e = constrain(y_e, eax, None, None)
    y = _combine_row(buf_tok, buf_w, y_e, n)
    return constrain(y.reshape(b, s, d), "batch", "seq", "d_model"), aux, load


def _expert_shard_map_fn(cfg, compute_dtype, n_data: int, n_model: int,
                         row_len: int):
    """Per-device body for the shard_map MoE (expert_sharding="ep_sm").

    The §Perf Cell-1 fix pjit could not express: run the expert FFN on
    f-shards and COMBINE the per-shard partials into the (10x smaller)
    token tensor BEFORE a single psum over "model" — instead of
    all-reducing the dispatched (tokens x k x capacity) buffer.

    Per-device inputs (shard_map slices):
      x_pad   (r_loc, L+1, d)   rows of this data shard (+ zero sentinel)
      buf_tok (r_loc, E, C)     dispatch buckets for those rows
      buf_w   (r_loc, E, C)
      w1/w3   (E_loc, d, f_loc) this device's expert/f shards
      w2      (E_loc, f_loc, d)
    Output: y (r_loc, L, d) — fully reduced.
    """
    def body(x_pad, buf_tok, buf_w, w1, w3, w2):
        r_loc, lp1, d = x_pad.shape
        e = buf_tok.shape[1]
        c = buf_tok.shape[2]
        e_loc = e // n_data
        # local gather of this shard's rows into all-expert buckets
        x_e = jax.vmap(lambda xp, bt: xp[bt])(x_pad, buf_tok)  # (r,E,C,d)
        # EP all-to-all over "data": split experts, concat rows ->
        # (r_loc * n_data, E_loc, C, d): every row shard's tokens for the
        # experts that live on this data shard
        # tiled a2a: split the expert axis across "data", concat source
        # shards on the row axis — one op, no 5D reshape round-trip (the
        # reshapes materialized two extra (r,E,C,d)-sized buffers)
        x_e = jax.lax.all_to_all(x_e, "data", split_axis=1, concat_axis=0,
                                 tiled=True)        # (r_loc*n_data, E_loc, C, d)
        h1 = jnp.einsum("recd,edf->recf", x_e, w1)
        h3 = jnp.einsum("recd,edf->recf", x_e, w3)
        y_e = jnp.einsum("recf,efd->recd", jax.nn.silu(h1) * h3, w2)
        # partial over "model" (f contracted locally).  Inverse tiled a2a
        # sends expert outputs back to their row shards, re-assembling
        # the full expert axis in original order.
        y_e = jax.lax.all_to_all(y_e, "data", split_axis=0, concat_axis=1,
                                 tiled=True)        # (r_loc, E, C, d)
        # ...combine to tokens while still partial-over-model...
        def combine(bt, bw, ye):
            y = jnp.zeros((lp1, d), ye.dtype)
            return y.at[bt].add(ye * bw[..., None])[:lp1 - 1]
        y = jax.vmap(combine)(buf_tok, buf_w, y_e)     # (r_loc, L, d)
        # ...then ONE reduction of the token tensor (10x smaller than the
        # dispatched buffer the pjit baseline all-reduces)
        return jax.lax.psum(y, "model")
    return body


def _moe_chunked_shardmap(cfg, p, x, compute_dtype):
    """expert_sharding="ep_sm": explicit-collective MoE (see above)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.parallel.sharding import active_mesh
    mesh = active_mesh()
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    row_len = min(s, ROW_LEN)
    n_rows = b * (s // row_len)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_data, n_model = axis_sizes.get("data", 1), axis_sizes.get("model", 1)
    xr = x.reshape(n_rows, row_len, d)
    nc = max(1, n_rows // max(n_data, ROWS_PER_CHUNK))
    r = n_rows // nc
    xrc = jnp.moveaxis(xr.reshape(r, nc, row_len, d), 1, 0)
    cap = max(1, math.ceil(CAPACITY_FACTOR * row_len * k / e))
    dispatch_v = jax.vmap(lambda i, w: _dispatch_row(i, w, row_len, e, cap))
    w1 = p["w1"].astype(compute_dtype)
    w3 = p["w3"].astype(compute_dtype)
    w2 = p["w2"].astype(compute_dtype)
    body = _expert_shard_map_fn(cfg, compute_dtype, n_data, n_model, row_len)
    smapped = shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"),
                  P("data", None, "model"), P("data", None, "model"),
                  P("data", "model", None)),
        out_specs=P("data"),
        check_vma=False)
    # recompute the expert segment in the backward instead of stashing
    # the a2a/dispatch intermediates per chunk (the stash was ~5 GB/chunk
    # x 59 layers of extra memory traffic — measured via top_bytes)
    smapped = jax.checkpoint(
        smapped, policy=jax.checkpoint_policies.nothing_saveable)

    def chunk_fn(carry, x_c):
        aux_acc, load_acc = carry
        x_c = constrain(x_c, "batch", None, None)
        ids, w, aux, load = route(cfg, p, x_c)
        buf_tok, buf_w = dispatch_v(ids, w)
        x_pad = jnp.concatenate(
            [x_c.astype(compute_dtype),
             jnp.zeros((r, 1, d), compute_dtype)], axis=1)
        y_c = smapped(x_pad, buf_tok, buf_w.astype(compute_dtype),
                      w1, w3, w2)
        return (aux_acc + aux, load_acc + load), y_c

    (aux, load), ys = jax.lax.scan(
        chunk_fn, (jnp.asarray(0.0, jnp.float32),
                   jnp.zeros((e,), jnp.float32)), xrc)
    ys = jnp.moveaxis(ys, 0, 1).reshape(n_rows, row_len, d)
    return ys.reshape(b, s, d).astype(x.dtype), aux / nc, load / nc


def _moe_chunked(cfg, p, x, compute_dtype):
    """Train/prefill path: rows of ROW_LEN tokens, chunks of 16 rows."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    row_len = min(s, ROW_LEN)
    assert s % row_len == 0, (s, row_len)
    n_rows = b * (s // row_len)
    xr = x.reshape(n_rows, row_len, d)
    nc = max(1, n_rows // ROWS_PER_CHUNK)
    r = n_rows // nc
    assert r * nc == n_rows, (n_rows, nc)
    # rows laid out (r, nc): chunk i takes one row from each shard's block
    xrc = xr.reshape(r, nc, row_len, d)
    xrc = jnp.moveaxis(xrc, 1, 0)                      # (nc, r, L, d)
    cap = max(1, math.ceil(CAPACITY_FACTOR * row_len * k / e))

    dispatch_v = jax.vmap(
        lambda i, w: _dispatch_row(i, w, row_len, e, cap))

    def body(carry, x_c):
        aux_acc, load_acc = carry
        x_c = constrain(x_c, "batch", None, None)      # (r, L, d) rows=data
        ids, w, aux, load = route(cfg, p, x_c)
        buf_tok, buf_w = dispatch_v(ids, w)            # (r, E, C)
        x_pad = jnp.concatenate(
            [x_c, jnp.zeros((r, 1, d), x_c.dtype)], axis=1)
        x_e = jax.vmap(lambda xp, bt: xp[bt])(x_pad, buf_tok)  # (r, E, C, d)
        eax = _eax(cfg)
        x_e = constrain(x_e, None, eax, None, None)        # EP all-to-all
        y_e = _expert_ffn(cfg, p, x_e, compute_dtype)
        y_e = constrain(y_e, None, eax, None, None)
        y_e = constrain(y_e, "batch", None, None, None)    # back to rows
        y_c = jax.vmap(_combine_row, in_axes=(0, 0, 0, None))(
            buf_tok, buf_w, y_e, row_len)
        return (aux_acc + aux, load_acc + load), y_c

    (aux, load), ys = jax.lax.scan(
        body, (jnp.asarray(0.0, jnp.float32), jnp.zeros((e,), jnp.float32)),
        xrc)
    ys = jnp.moveaxis(ys, 0, 1).reshape(n_rows, row_len, d)
    y = ys.reshape(b, s, d)
    return y, aux / nc, load / nc
