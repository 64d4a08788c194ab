"""Tests for the §Perf optimization knobs: numerical equivalence of the
optimized paths against the paper-faithful baselines."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models.attention import (_build_mask, _dot_attention,
                                    _sliding_attention_blocked)
from repro.models.model import Model


def test_blocked_sliding_attention_equals_naive():
    b, s, h, kv, d, w = 2, 384, 4, 2, 16, 96
    keys = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, s, kv, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, s, kv, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    mask = _build_mask(pos, pos, True, w)[:, None, None]
    ref = _dot_attention(q, k, v, mask, 0.25, 30.0, "naive")
    blk = _sliding_attention_blocked(q, k, v, pos, w, 0.25, 30.0, block_q=96)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_blocked_model_forward_equals_naive():
    cfg = get_smoke_config("gemma2-27b").replace(compute_dtype="float32")
    m_naive = Model(cfg)
    m_blk = Model(cfg.replace(attn_impl="blocked"))
    params = m_naive.init_params(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 48), 0, cfg.vocab)
    batch = {"tokens": toks, "targets": toks}
    l1, _, _, _ = m_naive.forward(params, batch, train=False)
    l2, _, _, _ = m_blk.forward(params, batch, train=False)
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32),
                               rtol=1e-4, atol=1e-4)


def test_int8_kv_cache_decode_tracks_fp():
    cfg = get_smoke_config("gemma2-2b").replace(compute_dtype="float32")
    cfg_q = cfg.replace(kv_cache_quant=True)
    toks = jax.random.randint(jax.random.key(1), (2, 25), 0, cfg.vocab)
    outs = {}
    for name, c in (("fp", cfg), ("int8", cfg_q)):
        m = Model(c)
        params = m.init_params(jax.random.key(0))
        cache = m.init_cache(jax.random.key(0), 2, 32)
        _, cache = m.prefill(params, {"tokens": toks[:, :24]}, cache)
        lg, _ = m.decode_step(params, cache, toks[:, 24:25],
                              jnp.asarray(24, jnp.int32))
        outs[name] = np.asarray(lg, np.float32)
    err = np.abs(outs["fp"] - outs["int8"]).max()
    assert err < 0.05, f"int8 KV cache drifted: {err}"
    # and the quantized cache really is int8
    m = Model(cfg_q)
    cache = m.init_cache(jax.random.key(0), 2, 32)
    leaves = jax.tree.leaves(cache)
    assert any(l.dtype == jnp.int8 for l in leaves)


def test_fused_chain_kernel_matches_two_pass():
    from repro.kernels.fused_chain import (fused_decrypt_dpi_pallas,
                                           fused_decrypt_dpi_ref)
    from repro.kernels.ref import expand_key
    from repro.kernels.dpi_mlp import init_dpi_params, ternarize
    rng = np.random.default_rng(0)
    pay = rng.integers(0, 256, (5, 1024), dtype=np.uint8)
    rk = expand_key(rng.integers(0, 256, 16, dtype=np.uint8))
    params = ternarize(init_dpi_params(jax.random.key(0)))
    p1, s1 = fused_decrypt_dpi_pallas(jnp.asarray(pay), rk, params)
    p2, s2 = fused_decrypt_dpi_ref(jnp.asarray(pay), rk, params)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)


def test_ep2d_moe_numerics_match_ep_tp():
    """Both expert layouts compute the same function."""
    import repro.models.moe as moe
    from repro.models import params as P
    cfg = get_smoke_config("deepseek-v3-671b").replace(
        compute_dtype="float32")
    spec = moe.moe_spec(cfg)           # ep_tp spec (same param shapes)
    p = P.init(spec, jax.random.key(0), "float32")
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model)) * 0.1
    y1, _, _ = moe.moe_ffn(cfg, p, x, jnp.float32)
    y2, _, _ = moe.moe_ffn(cfg.replace(expert_sharding="ep2d"), p, x,
                           jnp.float32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)


def test_ep_sm_shardmap_moe_matches_pjit_on_real_mesh():
    """The §Perf Cell-1 fix: shard_map MoE must equal the pjit MoE on a
    real multi-device mesh (collectives actually execute).  Needs its own
    process: 8 host devices must be configured before jax init."""
    import os
    import subprocess
    import sys
    snippet = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import repro.models.moe as moe
from repro.models import params as P
from repro.parallel import sharding as sh
from repro.configs import get_smoke_config
cfg = get_smoke_config('deepseek-v3-671b').replace(compute_dtype='float32')
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
p = P.init(moe.moe_spec(cfg), jax.random.key(0), "float32")
x = jax.random.normal(jax.random.key(1), (4, 4096, cfg.d_model)) * 0.1
with sh.activate(mesh, sh.make_rules("train"), "t"):
    y_tp = jax.jit(lambda p, x: moe.moe_ffn(cfg, p, x, jnp.float32)[0])(p, x)
    csm = cfg.replace(expert_sharding="ep_sm")
    y_sm = jax.jit(lambda p, x: moe.moe_ffn(csm, p, x, jnp.float32)[0])(p, x)
err = float(jnp.max(jnp.abs(y_tp - y_sm)))
assert err < 1e-5, err
print("EP_SM_OK", err)
"""
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    proc = subprocess.run([sys.executable, "-c", snippet],
                          capture_output=True, text=True, timeout=560,
                          env=env, cwd=root)
    assert "EP_SM_OK" in proc.stdout, proc.stderr[-800:]
