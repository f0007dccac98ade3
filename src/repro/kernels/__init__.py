"""Pallas TPU kernels for the SPLS hot spots (+ pure-jnp oracles in ref.py).

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are
validated on CPU in interpret mode against ref.py.
"""

from .flash_decode import flash_decode
from .gathered_matmul import gather_rows_kernel, gathered_matmul
from .moe_gmm import moe_gmm
from .paged_decode import paged_flash_decode
from .ops import (attention, flash_attention, hlog_qmatmul,
                  local_similarity_dist, predict_matmul, window_distances)
