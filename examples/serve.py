"""Batched serving example: prefill a batch of prompts, decode with the
KV-cache runtime (ring caches on sliding-window layers, recurrent states
on SSM layers), greedy sampling.

  PYTHONPATH=src python examples/serve.py
"""
from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.launch.serve import serve_batch
from repro.models.model import Model


def main():
    enable_compile_cache()
    for arch in ("gemma2-2b", "xlstm-125m", "recurrentgemma-9b"):
        cfg = get_smoke_config(arch)
        model = Model(cfg)
        tokens, t_p, t_d = serve_batch(cfg, model, batch_size=4,
                                       prompt_len=32, gen=16)
        print(f"[serve] {arch:18s} prefill {t_p*1e3:7.1f}ms  "
              f"decode {t_d*1e3:7.1f}ms  "
              f"sample={tokens[0][:6].tolist()}")
    print("serve OK")


if __name__ == "__main__":
    main()
