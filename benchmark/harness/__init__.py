"""The benchmark's harness: finds configurations, traffic mixes, limits and
per-layer metric readers by the names ``BENCHMARK.json`` gives them."""
