"""CPU tests of the chip benchmark.

Run from the repository root (they sit outside tier-1's ``testpaths``):

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""
