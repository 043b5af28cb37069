"""Pallas TPU kernels for the performance-critical compute layers, each with
a pure-jnp oracle in ref.py and a jit'd wrapper in ops.py.

  flash_attention     -- causal FA-2 schedule, VMEM-resident softmax state
  selective_scan      -- Mamba-1 recurrence, VMEM-resident (d,N) state
  ssd_scan            -- Mamba-2 SSD chunked matmul form (MXU-aligned)
  gossip_mix          -- fused consensus weighted accumulation (paper eq. 3)
  gossip_mix_weighted -- stacked-node variant with per-edge weight vectors
                         (ops.gossip_gather_mix = gather + this, the dense
                         simulator's k-regular fast path)
  nonsmooth_subgrad   -- the section V.B problem's subgradient in one pass
                         over its centres (ops.nonsmooth_subgrad_impl)
"""

from repro.kernels import ops, ref
