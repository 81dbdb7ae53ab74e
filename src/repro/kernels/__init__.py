"""Pallas TPU kernels for the paper's compute hot spots.

zdist   — blocked z-norm min-distance (HST inner loop), MXU tiles
mpblock — exact matrix profile, window tiles built in VMEM (SCAMP)
paa     — fused PAA + SAX digitization (bandwidth-bound)

Each package: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle).  Validated in interpret mode on CPU;
TPU is the target.
"""
