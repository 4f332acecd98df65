"""Pallas TPU kernels (validated on CPU with interpret=True):

  node_fused/  fused per-node FiGaRo pass (mask·head/tail·φ·emit) — hot path
  head_tail/   segmented generalized head/tail — the unfused building block
  tsqr_leaf/   Householder R of a stack of TSQR leaves in VMEM — float32
               post-processing on a TPU, every TSQR level
  flash_attn/  fused GQA attention — serving-side mixer hot spot

Platform policy (compiled on TPU/GPU, interpreted elsewhere, explicit
``interpret=`` override) is shared via `_platform.py`.
"""
