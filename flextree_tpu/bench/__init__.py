"""Benchmark harness: the reference benchmark.cpp rebuilt for JAX/TPU."""

from .harness import BenchConfig, BenchReport, measure_points, run_allreduce_bench

__all__ = ["BenchConfig", "BenchReport", "measure_points", "run_allreduce_bench"]
