"""repro: Routing-guided learned Product Quantization (RPQ) for graph-based ANNS.

A production-grade JAX framework reproducing and extending

    Yue et al., "Routing-Guided Learned Product Quantization for Graph-Based
    Approximate Nearest Neighbor Search" (PVLDB / CS.IR 2023).

Package layout
--------------
core/      the paper's contribution (differentiable quantizer, feature
           extractor, joint training)
pq/        baseline quantizers (PQ, OPQ, Catalyst-like)
graphs/    proximity-graph construction (kNN, Vamana, HNSW, NSG)
search/    batched beam-search routing + serving engines
kernels/   Pallas TPU kernels for the PQ hot loops (ADC scan, pairwise)
models/    assigned architecture zoo (LM dense/MoE, GNN, recsys)
data/      synthetic datasets, ground truth, input pipeline
dist/      sharding rules, checkpointing, fault tolerance, compression
configs/   per-architecture configs (--arch registry)
launch/    mesh / dryrun / train / serve drivers
"""

__version__ = "1.0.0"
