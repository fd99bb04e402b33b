"""repro — production-grade JAX framework reproducing SCALE
("Memory-Efficient LLM Pretraining via Minimalist Optimizer Design").

Subpackages:
  core       — SCALE + baseline optimizers, memory accounting
  models     — transformer / SSM / hybrid model zoo with sharding annotations
  data       — deterministic shard-aware token pipeline
  training   — train/serve step factories, grad accumulation, remat
  checkpoint — sharded zstd checkpoints with auto-resume
  launch     — meshes, the training CLI, compiled-step introspection
  configs    — assigned architecture configs (``--arch <id>``)
  kernels    — Pallas TPU kernels for the optimizer hot path
"""
__version__ = "1.0.0"
