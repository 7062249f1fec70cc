"""repro.kernels — Pallas TPU kernels for the compute hot-spots.

Kernels (each validated in interpret mode against the pure-jnp oracle in
ref.py, and compiled for a described v5e chip in tests/test_tpu_compile.py;
every entry point takes ``interpret`` from its caller, with no default):

  semiring_matmul  dense-tile semiring contraction — the TPU realization of
                   the paper's "hash-table" local SpGEMM accumulator
                   (DESIGN.md §4.2): MXU path for (+,×), VPU path for
                   min-plus / max-min / or-and
  segreduce        segmented semiring reduce (DESIGN.md §4.4) — the merge
                   engine's reduction stage; VMEM-resident output tiles as
                   running accumulators, registered behind
                   core.semiring.segment_reduce for tagged monoids
  bsr_spmm         block-sparse (ELL-blocked) × dense SpMM — the paper's
                   SpMM offload (§5) and the MoE grouped-matmul engine
  flash_attention  causal online-softmax attention (prefill hot-spot)
  ssd_chunk        Mamba2 SSD intra-chunk quadratic kernel

The paper's GPU offload policy (§5: "devices handle local multiplies, host
handles communication/merge; arithmetic-semiring only on device") maps to:
XLA handles collectives + sparse merges, these kernels handle dense-tile
contractions; non-jnp-expressible semirings fall back to the pure-JAX path.
"""
