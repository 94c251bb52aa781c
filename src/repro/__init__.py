"""repro — IntersectX (stream-intersection graph mining) on TPU, in JAX.

Layers:
  core/        the paper's stream ISA as composable JAX ops
  graph/       CSR graph substrate (padded, degree-bucketed, bitmaps)
  mining/      pattern-enumeration applications + baselines
  kernels/     Pallas TPU kernels (compiled on TPU, interpret mode on CPU)
  sparse/      S_VINTER applications: SpMM, TTV
  models/      assigned LM architecture zoo
  train/       training / serving runtime
  distributed/ sharding rules, compression, fault tolerance
  configs/     architecture configs
  launch/      mesh / dryrun / train / serve / mine entry points
"""

__version__ = "0.1.0"
