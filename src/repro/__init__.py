"""repro — Ripple (Clucas et al., 2021) reproduced as a multi-pod JAX
framework: polymorphic data layout, haloed distributed tensors, graph
scheduling, Pallas TPU kernels, and an LM train/serve stack on top."""

__version__ = "0.1.0"
