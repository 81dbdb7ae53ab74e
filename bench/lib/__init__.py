"""Harness core: cell and file lookup, trace reduction, work counts."""
