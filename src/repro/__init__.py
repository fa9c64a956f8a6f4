"""repro: RISC-V vector-architecture simulator + RiVec suite in JAX/Pallas.

Paper: Ramirez et al., "A RISC-V Simulator and Benchmark Suite for Designing
and Evaluating Vector Architectures", ACM TACO 17(4), 2020.
See README.md for usage and docs/architecture.md for the design.
"""
