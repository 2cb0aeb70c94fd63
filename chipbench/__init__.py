"""Chip benchmark of decentralized Moniqua training (``BENCHMARK.json``)."""
