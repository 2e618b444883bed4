"""simclr_tpu — a TPU-native SimCLR framework (JAX/XLA/pjit/Pallas).

A from-scratch re-design of the capabilities of nzw0301/SimCLR (multi-GPU
PyTorch SimCLR for CIFAR-10/100) for TPU hardware: one SPMD program per entry
point, jit-compiled train steps over a `jax.sharding.Mesh`, XLA collectives
over ICI instead of NCCL, global-batch BatchNorm instead of SyncBN, and an
optional all-gathered global negative set for NT-Xent.

Entry points (module-level, mirroring the reference CLI):
  python -m simclr_tpu.main          # contrastive pretraining
  python -m simclr_tpu.eval          # frozen-feature probes (centroid/linear/nonlinear)
  python -m simclr_tpu.supervised    # fully-supervised baseline
  python -m simclr_tpu.save_features # feature export (.npy)
"""

from simclr_tpu.config import Config, ConfigError, load_config

__version__ = "0.1.0"

__all__ = ["Config", "ConfigError", "load_config", "__version__"]
