"""Quickstart: train a small LM end-to-end with the framework's public
API — config registry, Model, Trainer (sharded, checkpointed, resumable).

  PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.compile_cache import enable_compile_cache
from repro.common.config import TrainConfig
from repro.configs import get_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models.model import Model
from repro.train.loop import Trainer, lm_batch_iterator


def main():
    enable_compile_cache()
    # 1. pick an architecture from the registry (reduced config: this
    #    container; the same ModelConfig at full size drives the
    #    multi-pod dry-run)
    cfg = get_smoke_config("gemma2-2b")
    print(f"arch={cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"pattern={cfg.pattern}")

    # 2. trainer with checkpointing + auto-resume
    tc = TrainConfig(steps=120, learning_rate=2e-3, warmup_steps=10,
                     checkpoint_every=50, log_every=20,
                     checkpoint_dir="/tmp/repro_quickstart")
    model = Model(cfg)
    trainer = Trainer(model, tc, mesh=make_host_mesh())

    # 3. train on a synthetic Markov stream (loss should fall fast)
    res = trainer.run(lm_batch_iterator(cfg, batch=8, seq=128))
    print(f"loss: {res.losses[0]:.3f} -> {res.final_loss:.3f} "
          f"in {res.wall_s:.1f}s"
          + (f" (resumed from step {res.resumed_from})"
             if res.resumed_from else ""))
    assert res.final_loss < res.losses[0], "did not learn"
    print("quickstart OK")


if __name__ == "__main__":
    main()
