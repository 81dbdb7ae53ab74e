"""Seeded input generators of the benchmark (the yardstick's own copies).

A configuration's ``data.generator`` names a file ``bench/data/<name>.py``
whose ``generate(n, seed, **params)`` makes one series of ``n`` points.
"""
