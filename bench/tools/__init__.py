"""Tools that measure what the benchmark's settings are set from."""
