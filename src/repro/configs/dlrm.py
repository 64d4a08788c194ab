"""dlrm — the paper's own workload (§8): DLRM online training behind the
BALBOA service chain (Neg2Zero -> Log on dense, Modulus on sparse),
streamed from disaggregated storage directly to accelerator memory.
[arXiv:1906.00091; paper Figs 9-11]"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple


@dataclass(frozen=True)
class DLRMConfig:
    """The paper's own workload (§8): DLRM behind the BALBOA service chain."""

    name: str = "dlrm"
    n_dense: int = 13               # Criteo-like dense feature count
    n_sparse: int = 26              # sparse (categorical) feature count
    embed_rows: int = 100_000       # rows per embedding table (after Modulus)
    embed_dim: int = 64
    bottom_mlp: Tuple[int, ...] = (512, 256, 64)
    top_mlp: Tuple[int, ...] = (512, 256, 1)
    modulus: int = 100_000          # paper §8.1 Modulus operator range
    param_dtype: str = "float32"
    compute_dtype: str = "float32"


def smoke_config() -> DLRMConfig:
    return DLRMConfig(embed_rows=1000, embed_dim=16,
                      bottom_mlp=(32, 16), top_mlp=(32, 1), modulus=1000)
