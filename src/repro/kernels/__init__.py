"""Pallas TPU kernels for compute hot-spots, each with a pure-jnp oracle.

Layout per kernel:
  <name>.py   pl.pallas_call + BlockSpec implementation (TPU target; validated
              on CPU via interpret=True)
  ref.py      pure-jnp oracles (the correctness ground truth)
  ops.py      jit'd dispatch wrappers: Pallas path on TPU, XLA path on every
              other backend (tests pass impl="pallas_interpret" explicitly)

Kernels:
  gram            Matérn-5/2 Gram matrix + fused Gram·vector (K^T·alpha
                  without materializing the cross-Gram) — the GP-bandit
                  hot-spots (paper §6.3 notes cubic-cost GP suggestion; the
                  Gram build is the bandwidth-bound part)
  flash_attention chunked online-softmax attention for the model zoo
  mamba2_ssd      chunked state-space-dual scan (zamba2 hybrid blocks)
"""

from repro.kernels import ops, ref
