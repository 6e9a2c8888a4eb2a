"""repro — Avoiding Materialisation for Guarded Aggregate Queries, in JAX.

A production-grade JAX framework that implements the paper's contribution
(0MA semi-join evaluation, frequency propagation, and the FreqJoin physical
operator) as the analytics layer of a multi-pod LM training/serving stack.

Layers:
  repro.core        — the paper: query IR, join trees, 0MA, rewrites, executor
  repro.tables      — fixed-shape columnar substrate
  repro.kernels     — the freq-join, semi-join and segment-sum kernels (XLA)
                      and their O(N·M) oracles
  repro.models      — LM zoo for the 10 assigned architectures
  repro.training    — optimizer / microbatching / remat / losses
  repro.service     — SQL serving tier: fingerprints, plan cache, QueryService
  repro.checkpoint  — sharded, elastic checkpointing
  repro.data        — synthetic relational + LM token pipelines
  repro.distributed — mesh rules, grad compression, collective helpers
  repro.configs     — one module per assigned architecture
  repro.launch      — mesh / dryrun / train / serve entry points
"""

__version__ = "1.0.0"
