# Pallas TPU kernels for the paper's compute hot-spots (compiled on a TPU,
# interpreted elsewhere — kernels/backend.py; BlockSpecs sized for the TPU
# memory hierarchy):
#   sc_mac   — fused B→S → AND → MUX-tree → popcount stochastic GEMM (§IV-B.1)
#   int8_mm  — int8×int8→int32 MXU GEMM + dequant epilogue (expected surrogate)
#   act_pool — fused 8-bit ReLU + p×p max-pool (§IV-B.2 add-on logic blocks)
#   paged_attn — decode attention over the paged device KV block pool
from repro.kernels.sc_mac import sc_matmul_pallas
from repro.kernels.int8_mm import int8_mm_pallas, int8_matmul
from repro.kernels.act_pool import act_pool
from repro.kernels.paged_attn import paged_attention
