"""The chip benchmark (BENCHMARK.json): the yardstick later PRs are held to."""
